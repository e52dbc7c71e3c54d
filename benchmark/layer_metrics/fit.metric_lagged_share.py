"""Layer: the `Module.fit` loop. Of the metric updates that the window's
steps queued (`queued` on the `device_compute via=update_metric` phases,
which `_fit_loop` writes around `update_metric` / `update_dict`), the share
that was folded behind a later dispatch (`lagged` of the same phases): the
loop then waited for the step BEFORE the one it had just handed to the
device, and the compiled call of the next step ran under a busy chip. An
update that a read of the metric forced is folded outside any such phase and
counts as queued alone, so a callback that reads every 50 batches leaves 98 %
at best, and one that reads every batch 0. The program's own step records,
`stepprof.timeline()`; None where no phase carries `queued` (a program that
reads the metric in the step that made it) or nothing was queued."""
from benchmark import timeline


def share(steps):
    """`lagged` over `queued`, in %, summed over the `device_compute` spans
    of ``steps`` that carry both; None where none does or nothing was
    queued."""
    lagged = queued = 0
    for step in steps:
        for name, _, _, attrs in step["spans"]:
            if name == "device_compute" and "queued" in attrs:
                lagged += attrs.get("lagged", 0)
                queued += attrs["queued"]
    return 100.0 * lagged / queued if queued else None


def read(run):
    steps = timeline.program_timeline()
    if steps is None:
        return None
    return share(timeline.window_steps(steps, run.result["t_open"],
                                       run.result["t_close"]))
