"""Device milliseconds of snapshot copies per second of window: every
execution of ``SketchEngine.snapshot``'s executable (``jit_copy_impl``, the
bank copy each publish makes and the slab copy after each seal) in the
traced window."""


def read(run):
    if run.trace is None:
        return None
    copy_s = run.trace["module_s"].get("jit_copy_impl", 0.0)
    return 1e3 * copy_s / run.trace["window_s"]
