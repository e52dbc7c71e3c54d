"""Layer: dispatch (`CompiledProgram`). Programs compiled, and ahead-of-time
programs that fell back, while the window ran: the program's own counters.
Should read 0."""


def read(run):
    c = run.result["counters"]
    return c["jit_compiles_total"] + c["jit_aot_fallbacks_total"]
