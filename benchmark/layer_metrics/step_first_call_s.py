"""Host seconds of the step's first call, by the program's own clock round
``fused.step`` (the event ``fused.step#1``): trace, lowering, compile or cache
read and the enqueue. The inside twin of ``first_dispatch_s``, which is the
driver's clock round the same call and the fetch of its result: the
difference of the two is the first execution. The line prints each recorded
call with the intervals that fell inside it."""

from benchmark import startup

ROW = {
    "name": "step_first_call_s", "unit": "s", "better": "lower",
    "source": "program_span", "layer": "entry and start-up", "moves": "setup_s",
}


def read(ctx):
    found = startup.summary(ctx)
    if found is None:
        return None
    for call in found["step_calls"]:
        print("step_first_call_s: " + startup.call_line(call))
    return next((call["step_s"] for call in found["step_calls"]
                 if call["name"].endswith("#1")), None)
