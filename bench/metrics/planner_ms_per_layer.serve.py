"""Host milliseconds of Lina's planner per MoE layer served: the summed
``phase1.estimate``, ``plan.lookup``, ``phase2.finetune`` and
``plan.build`` spans of ``runtime/server.py`` inside the window, over the
``server_layers_served_total`` counter's increase."""


def read(rec):
    layers = rec.counters.get("server_layers_served_total", 0)
    total = sum(rec.spans.values())
    if not layers or not total:
        return None
    return 1e3 * total / layers
