"""Checkpoint/resume via orbax, with keep-best semantics.

Reference equivalent (SURVEY.md §5 checkpoint/resume): ``ModelSaver`` →
``tf.train.Saver`` periodic writes, ``MaxSaver`` keep-best-score copy,
``--load`` → ``SaverRestore``. Here: orbax saves of the full TrainState
(params + opt state + step), a ``latest`` pointer, and a ``best`` pointer
updated when the monitored stat improves.
"""

from __future__ import annotations

import json
import os
from typing import Any, Optional

import jax
import orbax.checkpoint as ocp


class CheckpointManager:
    """Saves/restores TrainState pytrees under ``root/ckpt-<step>``."""

    def __init__(self, root: str, max_to_keep: int = 3):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        self._ckpt = ocp.StandardCheckpointer()
        self.max_to_keep = max_to_keep
        self._meta_path = os.path.join(self.root, "checkpoint.json")
        self._meta = {"all": [], "latest": None, "best": None, "best_score": None}
        if os.path.isfile(self._meta_path):
            with open(self._meta_path) as f:
                self._meta = json.load(f)
        self._run_meta_path = os.path.join(self.root, "run_meta.json")

    def write_run_meta(self, **fields):
        """Persist run-shape facts (steps_per_epoch, batch shape, ...) next to
        the checkpoints so a resume can detect a mismatched schedule: the
        epoch counter derives from step // steps_per_epoch, so resuming with
        a different shape silently stretches the LR/beta anneal."""
        if jax.process_index() != 0:
            return
        tmp = self._run_meta_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(fields, f)
        os.replace(tmp, self._run_meta_path)

    def read_run_meta(self) -> dict:
        if os.path.isfile(self._run_meta_path):
            with open(self._run_meta_path) as f:
                return json.load(f)
        return {}

    def _write_meta(self):
        tmp = self._meta_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self._meta, f)
        os.replace(tmp, self._meta_path)

    def _dir(self, step: int) -> str:
        return os.path.join(self.root, f"ckpt-{step}")

    def save(self, state: Any, step: int) -> str:
        """Save. In multi-process runs EVERY process must call this with the
        same path: orbax synchronizes all processes on save (a chief-only
        call deadlocks the chief in the barrier — seen in the 2-process CLI
        test). Metadata and pruning stay chief-only below."""
        path = self._dir(step)
        self._ckpt.save(path, jax.device_get(state), force=True)
        # StandardCheckpointer is async in this orbax version; commit before
        # pruning/meta so `latest` never points at an in-flight write.
        wait = getattr(self._ckpt, "wait_until_finished", None)
        if callable(wait):
            wait()
        if jax.process_index() != 0:
            return path
        if step not in self._meta["all"]:
            # re-saving an existing step (a killed run re-driven over the
            # same logdir) must not duplicate the bookkeeping entry
            self._meta["all"].append(step)
        self._meta["latest"] = step
        # prune oldest beyond max_to_keep; NEVER delete the best or the
        # just-saved latest (with max_to_keep=1 the old loop could delete the
        # checkpoint it had just written while `latest` still pointed at it)
        protected = {self._meta.get("best"), step}
        keep = list(self._meta["all"])
        deletable = [s for s in keep if s not in protected]
        while len(keep) > self.max_to_keep and deletable:
            victim = deletable.pop(0)
            keep.remove(victim)
            vdir = self._dir(victim)
            if os.path.isdir(vdir):
                import shutil

                shutil.rmtree(vdir)
        self._meta["all"] = keep
        self._write_meta()
        return path

    def mark_best(self, step: int, score: float) -> bool:
        """Record ``step`` as best if ``score`` improves; returns True if so."""
        best = self._meta.get("best_score")
        if best is None or score > best:
            self._meta["best"] = step
            self._meta["best_score"] = float(score)
            if jax.process_index() == 0:
                self._write_meta()
            return True
        return False

    @property
    def all_steps(self) -> list:
        """Every kept step, ascending, deduplicated (the eval-sweep
        enumeration surface; metadata written before the dedup-on-save fix
        may carry repeats)."""
        return sorted(set(self._meta.get("all", [])))

    @property
    def latest_step(self) -> Optional[int]:
        return self._meta.get("latest")

    @property
    def best_step(self) -> Optional[int]:
        return self._meta.get("best")

    def restore(self, target: Any, step: Optional[int] = None) -> Any:
        """Restore into the structure of ``target`` (an abstract or concrete
        TrainState). Defaults to the latest step."""
        if step is None:
            step = self.latest_step
        if step is None:
            # --load is outside input: a check that survives `python -O`
            raise FileNotFoundError(
                f"no checkpoint recorded under {self.root} "
                "(checkpoint.json has no 'latest')"
            )
        return self._ckpt.restore(self._dir(step), target)
