"""Share of the traced window in which no operation ran on a chip (%), busy
time averaged over the four chips of the host."""


def read(rec):
    dt = rec.get("device_trace")
    if not dt or dt["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dt["busy_s"] / dt["window_s"])
