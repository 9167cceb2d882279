"""fl_device_idle_pct: share of the traced window in which no operation ran
on the chip, under the federated round driver."""


def read(rc):
    t = rc.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
