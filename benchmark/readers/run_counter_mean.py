"""The mean over the window's steps of a counter the program carries in its
step metrics and the driver collected (``run["counters"][params["counter"]]``:
one number a step, already averaged over the layers that report it). Nothing
where the driver collected none."""


def read(run: dict, params: dict):
    values = (run.get("counters") or {}).get(params["counter"]) or []
    if not values:
        return None
    return sum(values) / len(values)
