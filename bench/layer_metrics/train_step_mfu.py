"""Train step: model FLOPs utilisation.  A step's model FLOPs over the
device time of a step (``step_s``, busy in the ``step`` spans, averaged
over the chips) times the chips times their peak.

The model FLOPs of a step are 6 N T, N the weights that multiply (the
attention and SwiGLU projections of every layer and the head; the
embedding is a lookup), T the tokens a step, plus 6 L S H d_head T for
causal attention (the score and value products, half of the S x S
pairs, forward and backward).  Recomputed work does not count.
"""

PEAK_FLOPS = {  # bf16, per chip; Google Cloud documentation, "TPU v5e"
    "TPU v5 lite": 197e12,
}


def step_flops(c: dict, batch: int, seq_len: int) -> float:
    d, ff = c["hidden_size"], c["intermediate_size"]
    h, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    layers, vocab = c["num_hidden_layers"], c["vocab_size"]
    n = layers * (2 * d * h * dh + 2 * d * kv * dh + 3 * d * ff) + vocab * d
    tokens = batch * seq_len
    return 6.0 * n * tokens + 6.0 * layers * seq_len * h * dh * tokens


def peak_flops() -> float:
    """The peak of the chips the run holds; a chip not in the table is an
    error, not a default."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_FLOPS:
        raise KeyError(f"no bf16 peak for {kind!r} in PEAK_FLOPS")
    return PEAK_FLOPS[kind]


def read(run):
    spans = run.trace.spans.get("step") if run.trace else None
    if not spans or not spans.busy_s or not run.steps or "hidden_size" not in run.config:
        return None
    flops = step_flops(run.config, int(run.traffic["batch"]), int(run.traffic["seq_len"]))
    step_s = spans.busy_s / run.steps
    return 100.0 * flops / (step_s * run.trace.n_devices * peak_flops())
