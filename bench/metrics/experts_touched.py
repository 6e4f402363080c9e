"""Mean share of the routed experts that a decode step gave at least one row,
per expert layer (``ServeResult.step_counters["experts_touched"]``, counted
on the device and fetched after each wave)."""


def read(ctx):
    m = ctx["m"]
    layers = m["num_hidden_layers"] - m["first_k_dense_replace"]
    total = n = 0
    for _, r in ctx["window"].waves:
        touched = getattr(r, "step_counters", {}).get("experts_touched")
        if touched is None or touched.shape != (r.steps, layers):
            return None
        total += float(touched.sum())
        n += touched.size
    if not n:
        return None
    return 100.0 * total / n / m["n_routed_experts"]
