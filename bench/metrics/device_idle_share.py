"""Share of the traced window in which no op ran on the device, mean over
the chips used: 100 x (1 - busy / window)."""


def reduce(traced):
    if not traced.modules or traced.window_s <= 0:
        return None
    return 100.0 * (1.0 - traced.busy_s() / traced.window_s)
