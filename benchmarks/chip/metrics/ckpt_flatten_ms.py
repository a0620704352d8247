"""Milliseconds per save of ``FlashCheckpoint.last_save_seconds`` (the
program's own count of the synchronous part of ``save``: the device-to-host
copy and the flatten), averaged over the window's saves."""


def read(ctx):
    saves = ctx["save_flatten_s"]
    if not saves:
        return None
    return 1e3 * sum(saves) / len(saves)
