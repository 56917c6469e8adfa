"""Seconds of the first step on a new allocation, from the call into the
re-jitted step (trace, lower, load from the compile cache, run) to its
state being ready, averaged over the events of the variant's kind."""
from ._spans import mean, per_event


def read(context, variant=None):
    return mean(per_event(context, "first_step", variant))
