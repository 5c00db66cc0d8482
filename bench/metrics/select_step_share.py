"""Share of the window's decode steps that refreshed a page selection
(EngineStats.select_steps / decode_steps)."""


def read(run):
    n = run.delta("decode_steps")
    return 100.0 * run.delta("select_steps") / n if n else None
