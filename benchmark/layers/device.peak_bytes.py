"""Peak device memory on the fullest chip after the window, as the result
line's ``memory_peak_bytes`` has it (``run.memory_peak_bytes``: peak of
live buffers plus the peak reserved for the programs' scratch): the
number a cell's size is judged by."""


def read(ctx):
    return ctx["device"].get("memory_peak_bytes")
