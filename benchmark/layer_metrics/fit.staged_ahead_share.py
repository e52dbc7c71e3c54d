"""Layer: the `Module.fit` loop. Of the bytes that the window's steps bound
to the executor's inputs (`bytes` of the `h2d` phases of `Module._load_batch`
and, in the scan path, of `stack_batches`), the share they found on the
device already (`staged_ahead` of the same phases): put there by
`Module.prepare` while the dispatch before ran. An epoch's first batch is
staged by its own step, so a window of n steps reads (n - 1) / n at best.
The program's own step records, `stepprof.timeline()`; None where no phase
carries `staged_ahead` (a program that does not stage ahead)."""
from benchmark import timeline


def share(steps):
    """`staged_ahead` over `bytes`, in %, summed over the `h2d` spans of
    ``steps`` that carry both; None where none does or nothing was bound."""
    ahead = bound = 0
    for step in steps:
        for name, _, _, attrs in step["spans"]:
            if name == "h2d" and "staged_ahead" in attrs:
                ahead += attrs["staged_ahead"]
                bound += attrs["bytes"]
    return 100.0 * ahead / bound if bound else None


def read(run):
    steps = timeline.program_timeline()
    if steps is None:
        return None
    return share(timeline.window_steps(steps, run.result["t_open"],
                                       run.result["t_close"]))
