"""Jitted calls the engine issued per engine step in the window
(EngineStats.dispatches / engine_steps)."""


def read(run):
    n = run.delta("engine_steps")
    return run.delta("dispatches") / n if n else None
