"""Device time of the train-step program per step, in milliseconds: the
``XLA Modules`` time of ``jit_train_step`` in the trace over the traced
steps."""


def read(context, variant=None):
    trace = context.get("trace")
    window = context.get("window")
    if not trace or not window or window["steps"] == 0:
        return None
    seconds = trace["programs"].get("jit_train_step", 0.0)
    if seconds <= 0:
        return None
    return 1e3 * seconds / window["steps"]
