"""Model FLOP/s utilisation of serving: the forward FLOPs of the prompt
positions and decoded tokens processed in the window (``bench/flops.py``:
weights met with the serving top-k, causal attention over the context, the
LM head per produced token) over window x chips x bf16 peak."""


def read(rec):
    flops = rec.work.get("model_flops", 0.0)
    if not flops or rec.window_s <= 0:
        return None
    return 100.0 * flops / (rec.window_s * rec.n_chips
                            * rec.peak["bf16_flops"])
