"""Device (one v5e): share of the traced window in which no operation ran
on the chip, in percent."""


def read(ctx):
    t = ctx["trace"]
    if not t["n_devices"] or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
