"""Device milliseconds per decode step outside its two kernels: the device
time of the scheduler's decode program, less that of the packed GEMV and
decode attention events inside its executions (what is left: the KV write,
cache copies, norms, rope, argmax).  Nothing is read unless the trace holds
one program execution per decode step, one GEMV per block linear per layer
and one attention call per layer of each."""
import bisect

PROGRAM = "jit_sched_decode_step"
GEMV, ATTN = "quant_gemv_op", "decode_attention_op"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window() is None or not tr.modules \
            or not tr.device_ops:
        return None
    from bench import trace as T
    c, m, q = ctx["costs"], ctx["m"], ctx["q"]
    lo, hi = tr.window()
    plane = sorted(tr.modules)[0]
    runs = sorted((s, e) for _, s, e in
                  T.named(tr.modules[plane], PROGRAM, lo, hi))
    steps = sum(r.steps for _, r in ctx["window"].waves)
    if not runs or len(runs) != steps:
        return None
    starts = [s for s, _ in runs]

    def in_a_run(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < runs[i][1]

    per_step = {GEMV: len(c.qlinear_calls(m, 1, q["bits"], q["group_size"])),
                ATTN: m["num_hidden_layers"]}
    kernel_ns = 0.0
    for name, n in per_step.items():
        ev = [(s, e) for _, s, e in T.named(tr.device_ops[plane], name, lo, hi)
              if in_a_run(s)]
        if len(ev) != n * steps:
            return None
        kernel_ns += sum(e - s for s, e in ev)
    run_ns = sum(e - s for s, e in runs)
    return 1e3 * (run_ns - kernel_ns) * 1e-9 / steps
