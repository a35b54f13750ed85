"""1 - union of device-op intervals over the traced window, of the
fullest device."""


def read(ctx):
    return 100.0 * ctx["trace"].idle_share()
