"""Set-up: the process's wall time in JAX's tracing, lowering and compiling
(or fetching from the persistent compilation cache), as the program's own
compile counter (``repro.obs.spans.compile_counter``) has summed it since
the trainer was built. The window compiles nothing (the harness counts
it), so this is set-up time."""


def read(ctx):
    from repro.obs import spans
    counter = getattr(spans, "compile_counter", None)
    if counter is None or not counter().count:
        return None
    return counter().seconds
