"""Seconds for the whole ``TrainState`` to land on the new mesh: the
``reshard`` span around ``ElasticTrainer._reshard_state``, which a
traced run blocks on, averaged over the events of the variant's kind."""
from ._spans import mean, per_event


def read(context, variant=None):
    return mean(per_event(context, "reshard", variant))
