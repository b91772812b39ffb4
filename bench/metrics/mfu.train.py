"""Model FLOP/s utilisation of training: 6 x the weights a token meets
(top-k experts, LM head) plus causal attention, per token
(``bench/flops.py``), times the tokens of the steps in the window, over
window x chips x bf16 peak.  Recomputation does not count."""


def read(rec):
    flops = rec.work.get("model_flops", 0.0)
    if not flops or rec.window_s <= 0:
        return None
    return 100.0 * flops / (rec.window_s * rec.n_chips
                            * rec.peak["bf16_flops"])
