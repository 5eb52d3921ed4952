"""kernels: programs lowered inside the window (jax's own count).
Anything but 0 means a shape or a constant leaked into a trace."""


def read(run):
    return run["window_compiles"]
