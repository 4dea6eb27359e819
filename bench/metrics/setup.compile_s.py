"""Host seconds of jax's compile path during set-up: tracing, lowering,
and the backend compile or the load from the persistent compile cache
(``repro.launch.compile_cache.compile_seconds``, the union of their
spans).  The counter runs from the harness's ``enable_compile_cache`` on;
the window compiles nothing (``window_compiles`` is held at 0), so what
it holds when read is set-up's.  Nothing where the program has no such
counter."""


def read(run):
    from repro.launch import compile_cache
    seconds = getattr(compile_cache, "compile_seconds", None)
    if seconds is None:
        return None
    return seconds()["total"]
