"""Share of the traced window in which no operation ran on the device,
averaged over the chips (``device_trace``)."""


def read(context, variant=None):
    trace = context.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 1.0 - trace["busy_s"] / trace["window_s"]
