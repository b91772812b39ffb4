"""Share of MoE layer batches on which phase 2 re-planned (a blocking
fine-tune): ``server_phase2_finetunes_total`` over
``server_layers_served_total``, both as increases over the window."""


def read(rec):
    layers = rec.counters.get("server_layers_served_total", 0)
    if not layers:
        return None
    return 100.0 * rec.counters.get("server_phase2_finetunes_total", 0) / layers
