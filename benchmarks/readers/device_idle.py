"""1 - (union of device-op intervals / traced slice), in percent."""


def read(run: dict, args: dict):
    tr = run.get("trace")
    if not tr or not tr["n_devices"] or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
