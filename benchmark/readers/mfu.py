"""The whole step's share of the chips' peak: the operations the model
needs for the tokens of the window (the benchmark's count), over the
window, over chips times the bf16 peak."""


def read(facts, reduced, params, peaks):
    flops, window = facts.get("model_flops"), facts.get("window_s")
    if not flops or not window or not facts.get("on_chip"):
        return None
    return 100.0 * flops / (window * facts["chips"] * peaks["flops_bf16"])
