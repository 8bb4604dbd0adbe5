"""Share of the traced window in which no op ran on the device: 1 minus
the union of device-op intervals over the window, in percent."""


def read(record):
    return 100.0 * record["trace"]["idle_share"]
