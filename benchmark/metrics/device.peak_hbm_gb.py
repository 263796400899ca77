"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read when
the window closed (before the reference ran)."""


def read(ctx):
    b = ctx["memory_peak_bytes"]
    return b / 1e9 if b else None
