"""Host time of the event dispatch of a resize, in milliseconds: the
``runtime`` spans around ``ElasticTrainer._handle`` (the engine's plan,
spawn or TS bookkeeping) and ``_make_ctx`` (the mesh rebuild), summed
per event and averaged over the events of the variant's kind."""
from ._spans import mean, per_event


def read(context, variant=None):
    value = mean(per_event(context, "runtime", variant))
    return None if value is None else 1e3 * value
