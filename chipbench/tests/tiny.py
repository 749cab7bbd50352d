"""A cell of BENCHMARK.json cut to a size the CPU runs in seconds: the
same architecture, numerics, harness and reference, with two layers of
width 128.  In float32 the two agree to rounding, except where rounding
moves a value across a BFP step (a step is 1/64 to 1/128 of its group's
largest value at 8 bits), which moves a logit by up to about 0.02 here;
the faults move them by about one standard deviation, 1.  The cells'
own limits are in ``limits/``."""
import spec

LIMIT = 0.1


def cell(name: str, dtype: str = "float32"):
    bench = spec.load_benchmark()
    c = spec.cell(bench, name)
    m = c["config"]["model"]
    mha = m["n_kv_heads"] == m["n_heads"]
    m.update(n_layers=2, d_model=128, n_heads=4, n_kv_heads=4 if mha else 2,
             head_dim=32, d_ff=256, vocab_size=512, param_dtype=dtype)
    c["traffic"].update(
        max_seq=256, requests=4, batch_size=4,
        prompt_tokens={"min": 64, "max": 64},
        output_tokens={"min": 20, "max": 60, "median": 30, "sigma": 0.5})
    c["limits"] = {"max_logit_gap": LIMIT, "sample_requests": 3}
    return bench, c
