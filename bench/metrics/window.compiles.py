"""JIT compiles, or loads from the compilation cache, inside the traced
window: the program's ``jax.compile`` spans under the window's
``repro.obs`` tracer.  Every shape is warmed up before the window, so
this should read 0.  A program that does not record its compiles reads
nothing."""


def read(w):
    from repro.obs import trace
    name = getattr(trace, "COMPILE_SPAN", None)
    return None if name is None else len(w.spans.get(name, ()))
