"""A toy layout for the tests: layers of two kinds (``model["layer_types"]``
says which is which), one leaf name that both kinds have at different shapes,
and leaves drawn in each way ``perfbench.weights.leaf`` knows beyond its
defaults.  No configuration of the benchmark names it."""


def kinds(model):
    return list(model["layer_types"])


def layer(model, kind):
    h, heads = model["hidden_size"], model["heads"]
    if kind == "attn":
        return {"ln_attn/scale": (h,), "proj/kernel": (h, h),
                "proj/bias": (h,)}
    return {"decay": {"shape": (heads,), "uniform": [0.5, 2.0]},
            "gate_norm/scale": {"shape": (h,), "constant": 1.0},
            "conv/kernel": {"shape": (h, 4), "fan_in": 4},
            "proj/kernel": (h, 2 * h)}


def top(model):
    return {"word_emb/embedding": (model["vocab_size"], model["hidden_size"]),
            "ln_final/scale": (model["hidden_size"],)}
