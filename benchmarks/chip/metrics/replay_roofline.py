"""The bucket replay program's share of its roofline: the least time the
traced steps' replay work could take on the cell's chips (``work.py``:
operations or bytes, whichever bounds), over the device time of the
``_bucket_makespans`` modules in those steps, in percent."""
from benchmarks.chip.work import Work, roofline_s


def read(run):
    red, steps = run.reduction, run.traced_steps
    if red is None or not steps or not run.peaks:
        return None
    device_s = red.module_s.get("_bucket_makespans", 0.0)
    if device_s <= 0.0:
        return None
    w = run.player.work
    t, _ = roofline_s(Work(w.ops * steps, w.bytes * steps),
                      run.peaks, run.chips)
    return 100.0 * t / device_s
