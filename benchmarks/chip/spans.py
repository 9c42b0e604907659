"""The program's own ``repro.obs`` spans of a run, summed per call.

A call is one ``sim.pipeline.dispatch`` span: the dispatch loop of one
``pipelined_sweep_makespans`` call.  Every span of a call carries the
call's ``call`` tag, so a part's spans are summed over the calls and
divided by their number.  A program without the tag or the part gives
nothing to read.
"""

DISPATCH = "sim.pipeline.dispatch"


def per_call_ms(run, name: str):
    """Mean over the window's calls of the summed durations of the spans
    named ``name`` in each call, in milliseconds; ``None`` when the run
    recorded no such spans."""
    calls = {e["args"].get("call") for e in run.obs_events
             if e["name"] == DISPATCH} - {None}
    parts = [e["dur"] for e in run.obs_events
             if e["name"] == name and e["args"].get("call") in calls]
    if not parts:
        return None
    return 1e3 * sum(parts) / len(calls)
