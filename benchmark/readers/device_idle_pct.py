"""1 - (union of the device's operation intervals) / (traced slice), in %,
averaged over the chips. Nothing without a trace."""


def read(run: dict, params: dict):
    trace = run.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
