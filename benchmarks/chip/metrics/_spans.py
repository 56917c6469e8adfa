"""Shared by the span readers: per resize event, the summed seconds of
the harness spans of one name, for events of one kind."""


def per_event(context, name, kind):
    totals = {}
    for s in context.get("spans", ()):
        if s["name"] == name and s["tag"] == kind:
            totals[s["event"]] = totals.get(s["event"], 0.0) + s["t1"] - s["t0"]
    return list(totals.values())


def mean(values):
    return sum(values) / len(values) if values else None
