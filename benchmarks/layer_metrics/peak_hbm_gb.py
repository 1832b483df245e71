"""Peak device memory of the process (fullest chip) after the window, in
GB (1e9 bytes), from `memory_stats()`."""


def read(ctx):
    if not ctx["memory_peak_bytes"]:
        return None
    return ctx["memory_peak_bytes"] / 1e9
