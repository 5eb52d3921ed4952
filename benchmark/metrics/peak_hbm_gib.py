"""device: peak bytes held on the fullest device (buffers plus what is
reserved for the programs' temporaries), after the window."""


def read(run):
    if not run["peak_bytes"]:
        return None
    return run["peak_bytes"] / 2.0 ** 30
