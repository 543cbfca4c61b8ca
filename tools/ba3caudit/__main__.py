"""CLI: ``python -m tools.ba3caudit``.

Exit status: 0 = every invariant holds, 1 = findings, 2 = bad usage.

The process pins itself to the CPU platform BEFORE importing jax:
 - the audit is an IR property, and the manifest's XLA cost numbers are the
   CPU client's; a chip belongs to one process, so an audit that claimed it
   would fail beside a running trainer (docs/OPERATIONS.md);
 - the canonical mesh needs ≥2 devices, so a host-platform device count is
   forced when none is configured. The registry always builds its mesh from
   the FIRST two devices, so running under the 8-device pytest harness
   yields the same manifest numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional


def _pin_cpu_platform() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2"
        ).strip()


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.ba3caudit",
        description="Trace-level (jaxpr/HLO) invariant audit of the "
        "registered hot-path entry points (rule catalog: "
        "docs/static_analysis.md).",
    )
    parser.add_argument(
        "--entries",
        help="comma-separated entry-point names (default: all registered)",
    )
    parser.add_argument(
        "--json", action="store_true",
        help="machine output: one JSON object on stdout",
    )
    parser.add_argument(
        "--update-manifest", action="store_true",
        help="rewrite audit_manifest.json from the live measurement "
        "(review + commit the diff)",
    )
    parser.add_argument(
        "--manifest", help="manifest path (default: repo-root audit_manifest.json)",
    )
    parser.add_argument(
        "--tolerance", type=float, default=0.25,
        help="relative tolerance for flops/bytes drift (default: 0.25)",
    )
    parser.add_argument(
        "--list-entries", action="store_true",
        help="print the registered entry points and exit",
    )
    args = parser.parse_args(argv)

    _pin_cpu_platform()

    from distributed_ba3c_tpu import audit
    from tools import ba3caudit

    registered = audit.entry_names()
    if args.list_entries:
        for name in registered:
            print(name)
        return 0

    entries = None
    if args.entries:
        entries = [s.strip() for s in args.entries.split(",") if s.strip()]
        unknown = sorted(set(entries) - set(registered))
        if unknown:
            print(
                f"unknown entry point(s): {', '.join(unknown)}; "
                f"registered: {registered}",
                file=sys.stderr,
            )
            return 2

    measurements, findings = ba3caudit.run_audit(
        entries=entries,
        manifest_path=args.manifest,
        update_manifest=args.update_manifest,
        tolerance=args.tolerance,
    )

    # diagnostic, not a gate: T5 values are XLA outputs, so a manifest
    # measured under a different jax is the FIRST thing to check when
    # drift findings look like nobody's change
    import jax

    from tools.ba3caudit import manifest as manifest_mod

    meta = (manifest_mod.load(args.manifest or manifest_mod.DEFAULT_MANIFEST)
            or {}).get(manifest_mod.META_KEY, {})
    if meta.get("jax") and meta["jax"] != jax.__version__:
        print(
            f"ba3caudit: note — manifest measured under jax {meta['jax']}, "
            f"running under {jax.__version__}; T5 drift may be toolchain, "
            "not code (CI pins jax for this reason)",
            file=sys.stderr,
        )

    if args.json:
        print(json.dumps({
            "entries": {
                name: m.manifest_entry() for name, m in measurements.items()
            },
            "findings": [f.to_dict() for f in findings],
        }, indent=2, sort_keys=True))
    else:
        for name, m in sorted(measurements.items()):
            entry_findings = [f for f in findings if f.entry == name]
            status = "FAIL" if entry_findings else "ok"
            print(
                f"{name:24s} {status:4s} flops={m.flops:.4g} "
                f"bytes={m.bytes_accessed:.4g} "
                f"collectives={dict(sorted(m.collectives.items()))} "
                f"convs={len(m.conv_dtypes)} aliased={len(m.aliased_inputs)}"
            )
        for f in findings:
            print(f"{f.entry}: [{f.rule}] {f.message}")
        n = len(findings)
        print(f"ba3caudit: {n} finding{'s' if n != 1 else ''}")
        if args.update_manifest:
            print("ba3caudit: manifest updated — review + commit the diff")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
