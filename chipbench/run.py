"""Chip benchmark: one cell of ``BENCHMARK.json``, served on a TPU.

  python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
  python chipbench/run.py --plan        # CPU: each cell's prefill and decode shapes
  python chipbench/run.py --plan --root <copy>   # the cells of another benchmark

A run, in order:

1. refuses any platform but a TPU with fewer chips than the cell asks
   for (non-zero exit, no result), and turns on the compile cache;
2. draws the INT4 weights on the device from the seed in one jitted
   call (with the reference's draw of the norm and bias parameters in
   place of the initializer's ones and zeros), and builds the ``Engine``
   (XLA path, greedy) and a recording ``ServeLoop`` at the mix's batch
   and ``max_seq``.  A cell of more than one chip serves on a (data=1,
   model=chips) mesh of them, with the program's own layer-by-layer draw
   (``models/init.py:init_packed_params(mesh=)``): each layer is drawn
   whole on the first chip and placed in its tensor-parallel sharding
   (``distributed/sharding.py:param_pspecs``) before the next is drawn;
3. makes the job from the seed (``traffic.py``) and plays it once to warm
   every program the job uses;
4. ``--trace 0``: plays the job back to back for ``--seconds`` (whole
   jobs, at least one; another starts only while the last one's time says
   it ends in the window) and reports the end-to-end metrics;
   ``--trace 1``: plays one job under the profiler and reports the
   per-layer metrics read from its trace;
5. frees the program's state and holds a sample of the served requests to
   the configuration's plain reference (``references/``): ``correct``
   when the widest gap by which a served token's reference logit lies
   below the reference's best stays within the cell's limit and every
   request was served.

``setup_s`` runs from the start of this script to the start of the window.
The last line of stdout is the result; the numbers compared are the last
lines of stderr and the result's last key, ``check``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from functools import lru_cache, partial  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
# the TPU runtime's logs would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

import counts  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402

MAX_STEPS = 32                 # decode steps per ServeLoop chunk
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
OUT_OF_VOCAB_GAP = 1e30        # the gap read for a token outside the vocabulary


class CompileCounter:
    """Counts the programs JAX compiles or loads while ``on``."""

    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event == COMPILE_EVENT:
            self.n += 1


def seed_key(seed: int):
    """PRNG key of a seed of any size (64 bits used)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs import get_arch
    return dataclasses.replace(get_arch(conf["arch"]).config,
                               **conf["model"])


def quant_config(numerics: dict):
    """The program's ``QuantConfig`` for the numerics a configuration
    states; raises where the program cannot run them as stated."""
    from repro.core import kvcache
    from repro.core.quant_config import harmonia
    q = harmonia(numerics["kv_bits"]).replace(
        act_mantissa_bits=numerics["act_bits"],
        score_mantissa_bits=numerics["score_bits"])
    fixed = {"kv_high_bits": 8, "init_tokens": kvcache.INIT_TOKENS,
             "local_tokens": kvcache.LOCAL_TOKENS,
             "online_topk": q.smoothing.online_topk,
             "weight_bits": q.weight_bits,
             "weight_group": q.weight_group_size}
    for k, v in fixed.items():
        if numerics[k] != v:
            raise ValueError(f"numerics {k}={numerics[k]}: the program "
                             f"runs {v}")
    return q


def prompt_ids(mix: dict, prompt: str) -> list:
    return [int(mix["bos_id"])] + list(prompt.encode("ascii"))


def put_affine(params: dict, affine: dict) -> dict:
    """``params`` with the norm and bias parameters of ``affine`` (see
    ``references/dense.py:affine_params``) in place of the initializer's
    ones and zeros.  Each must replace a leaf of the same shape: a
    parameter the program does not hold raises."""
    out = dict(params)
    (kind, stack), = params["blocks"].items()      # one kind of block
    stack = dict(stack)
    for tree, values in ((out, affine["top"]), (stack, affine["layers"])):
        for name, v in values.items():
            if name not in tree or tree[name].shape != v.shape:
                raise ValueError(f"the program holds no {name} of shape "
                                 f"{v.shape}")
            tree[name] = v.astype(tree[name].dtype)
    out["blocks"] = {kind: stack}
    return out


def serve_mesh(devices, chips: int):
    """The mesh a cell of ``chips`` chips serves on: (data=1,
    model=chips) over the first ``chips`` of ``devices``, tensor-parallel
    on ``model``.  ``None`` for one chip: the engine's single-device
    path."""
    if chips == 1:
        return None
    if len(devices) < chips:
        raise ValueError(f"the cell needs {chips} devices, have "
                         f"{len(devices)}")
    from jax.sharding import Mesh
    return Mesh(np.asarray(devices[:chips]).reshape(1, chips),
                ("data", "model"))


@lru_cache(maxsize=None)
def _init_weights(cfg, reference: str, model: tuple, mesh=None):
    import jax
    from repro.models.init import init_packed_params
    affine = spec.reference(reference).affine_params
    if mesh is None:
        def init(key):
            return put_affine(init_packed_params(cfg, key),
                              affine(dict(model), key))
        return jax.jit(init)

    def init_sharded(key):
        # the program's own draw on a mesh: each layer drawn and packed,
        # then placed in its shards before the next is drawn
        params = init_packed_params(cfg, key, mesh=mesh)
        return jax.jit(lambda p, k: put_affine(p, affine(dict(model), k)),
                       out_shardings=jax.tree.map(lambda x: x.sharding,
                                                  params),
                       donate_argnums=0)(params, key)
    return init_sharded


def build(conf: dict, mix: dict, seed: int, quant=None, mesh=None):
    """Weights (from the seed: on the device in one jitted call, or on
    ``mesh`` layer by layer into their shards), the engine and the
    recording loop."""
    import jax
    from loop import RecordingServeLoop
    from repro.serving.engine import Engine, EngineConfig
    cfg = model_config(conf)
    quant = quant_config(conf["numerics"]) if quant is None else quant
    params = _init_weights(cfg, conf["reference"],
                           tuple(sorted(conf["model"].items())), mesh)(
                               seed_key(seed))
    jax.block_until_ready(params)
    engine = Engine(params, cfg, EngineConfig(
        max_seq=int(mix["max_seq"]), quant=quant, sampler="greedy",
        mesh=mesh))
    return RecordingServeLoop(engine, int(mix["batch_size"]), MAX_STEPS)


def job_numbers(conf: dict, mix: dict, prompts, job: dict) -> dict:
    """What the per-layer metrics read of one played job."""
    m = conf["model"]
    lens = [len(prompt_ids(mix, p)) for p in prompts]
    served = [len(s) for s in job["served"] if s is not None]
    flops = sum(counts.request_flops(m, P, len(s))
                for P, s in zip(lens, job["served"]) if s is not None)
    return dict(job["counts"], requests=len(served),
                served_tokens=sum(served), prompt_tokens=sum(lens),
                batch_size=int(mix["batch_size"]), flops=flops,
                decode_weight_bytes=counts.decode_weight_bytes(m))


def sample(jobs: list, n_sample: int, seed: int) -> list:
    """Indices of the requests held to the reference: the one served the
    most tokens, and ``n_sample - 1`` more drawn from the seed."""
    served = jobs[-1]["served"]
    n = len(served)
    size = [len(s) if s is not None else -1 for s in served]
    longest = int(np.argmax(size))
    rng = np.random.default_rng([seed, 1])
    rest = [i for i in rng.permutation(n) if i != longest]
    return sorted([longest] + [int(i) for i in rest[: n_sample - 1]])


def check(conf: dict, mix: dict, lim: dict, seed: int, prompts, jobs,
          failed: int):
    """The sampled requests of every job against the reference: the
    numbers compared, each with its limit, and (requests, served tokens)
    compared."""
    ref = spec.reference(conf["reference"])
    idx = sample(jobs, int(lim["sample_requests"]), seed)
    requests = []
    for i in idx:
        for served in {tuple(j["served"][i]) for j in jobs
                       if j["served"][i] is not None}:
            if served:
                requests.append((prompt_ids(mix, prompts[i]), served))
    vocab = conf["model"]["vocab_size"]
    bad = sum(1 for _, s in requests for t in s if not 0 <= t < vocab)
    gap = OUT_OF_VOCAB_GAP if bad else 0.0
    n_tok = 0
    if requests and not bad:
        logits = ref.served_logits(conf["model"], conf["numerics"],
                                   seed_key(seed), requests)
        for lg, (_, s) in zip(logits, requests):
            s = np.asarray(s)
            gaps = lg.max(-1) - lg[np.arange(len(s)), s]
            gap = max(gap, float(gaps.max()))
            n_tok += len(s)
    return ({"max_logit_gap": {"value": gap,
                               "limit": float(lim["max_logit_gap"])},
             "failed_requests": {"value": failed, "limit": 0}},
            (len(requests), n_tok))


def layer_ctx(reduced: dict, numbers: dict, conf: dict, mix: dict,
              device_kind: str, chips: int) -> dict:
    """What each per-layer metric's reader is handed: the reduced trace,
    the job's numbers, the configuration's sizes, the mix, one chip's
    peak rates and the number of chips that share the work (rooflines of
    shared work are ``chips`` times one chip's).  Raises where the trace
    holds another number of chips than the cell runs on."""
    if reduced["chips"] != chips:
        raise ValueError(f"the trace holds {reduced['chips']} TPU planes; "
                         f"the cell runs on {chips} chips")
    return {"trace": reduced, "job": numbers, "model": conf["model"],
            "mix": mix, "peaks": counts.peaks(device_kind), "chips": chips}


def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             trace: bool, devices=None, log=None, quant=None) -> dict:
    """One run.  ``devices`` skips the look for a chip (the cell serves on
    the first ``chips`` of them), and ``quant`` serves with other
    numerics than the configuration states (the harness's tests and
    ``control.py``)."""
    import jax
    from loop import play
    from repro.launch.device import enable_compile_cache, require_tpu
    w, conf, mix, lim = (cell["workload"], cell["config"], cell["traffic"],
                         cell["limits"])
    log = log or (lambda msg: print(f"[chipbench] {msg}", file=sys.stderr,
                                    flush=True))
    if devices is None:
        devices = require_tpu(int(w["chips"]))
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    chips = int(w["chips"])
    devices = devices[:chips]
    counter = CompileCounter()
    loop = build(conf, mix, seed, quant, serve_mesh(devices, chips))
    log(f"weights and engine ready at {time.perf_counter() - T_START:.1f} s")
    prompts, budgets = traffic.job(mix, seed)
    play(loop, prompts, budgets)                       # warm pass
    log(f"warm pass done at {time.perf_counter() - T_START:.1f} s")

    jobs = []
    counter.on = True
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    while True:
        tj = time.perf_counter()
        with jax.profiler.TraceAnnotation("job"):
            job = play(loop, prompts, budgets)
        job["seconds"] = time.perf_counter() - tj
        jobs.append(job)
        log(f"job {len(jobs)}: {job['seconds']:.3f} s, {job['counts']}")
        if trace or (time.perf_counter() - t0) + job["seconds"] > seconds:
            break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
        log(f"trace written at {time.perf_counter() - T_START:.1f} s")
    counter.on = False
    log(f"compiles_in_window {counter.n}")
    log(f"window: {len(jobs)} jobs in {window_s:.3f} s")

    dev = devices[0]
    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                       for s in stats)}
    log(f"memory stats of the first chip: {stats[0]}")
    attempted = len(prompts) * len(jobs)
    failed = sum(s is None for j in jobs for s in j["served"])
    served_tokens = sum(len(s) for j in jobs for s in j["served"]
                        if s is not None)
    result = {"correct": False, "attempted": attempted, "failed": failed}
    numbers = job_numbers(conf, mix, prompts, jobs[-1])
    del loop
    gc.collect()

    if trace:
        import trace as trace_lib
        t_red = time.perf_counter()
        reduced = trace_lib.reduce_dir(TRACE_DIR)
        log(f"trace of {os.path.getsize(trace_lib.find_xplane(TRACE_DIR))} "
            f"bytes reduced in {time.perf_counter() - t_red:.1f} s")
        log("executables traced (s): " + ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(
                reduced["modules"].items(), key=lambda kv: -kv[1])[:8]))
        ctx = layer_ctx(reduced, numbers, conf, mix, dev.device_kind, chips)
        metrics = {}
        for m in bench["per_layer"]:
            value = spec.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        trace_lib.load.cache_clear()           # the trace's events, read
        trace_lib.device_planes.cache_clear()  # once for all the readers
        log(f"per-layer metrics read at {time.perf_counter() - T_START:.1f} s")
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
    else:
        values = {"output_tok_s": served_tokens / window_s,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"]}
        breakdown = None

    t_ref = time.perf_counter()
    checked, (n_req, n_tok) = check(conf, mix, lim, seed, prompts, jobs,
                                    failed)
    log(f"reference: {n_req} requests, {n_tok} served tokens compared in "
        f"{time.perf_counter() - t_ref:.1f} s")
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in checked.values())
    result.update(metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = checked
    for name, c in checked.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def plan(bench: dict) -> list:
    """Each cell's job played on the CPU through the same recording
    ``ServeLoop`` at the architecture's smoke sizes, with the cell's
    lengths, budgets, batch and ``max_seq``: the prefill and decode shapes
    the job produces, in order of first use."""
    import jax
    from loop import play
    from repro.configs import get_arch
    if jax.devices()[0].platform != "cpu":
        raise SystemExit("--plan runs on the CPU (JAX_PLATFORMS=cpu)")
    out = []
    for w in bench["workloads"]:
        c = spec.cell(bench, w["name"])
        smoke = get_arch(c["config"]["arch"]).smoke
        conf = dict(c["config"], model={
            f.name: getattr(smoke, f.name) for f in dataclasses.fields(smoke)
            if f.name in c["config"]["model"]})
        loop = build(conf, c["traffic"], 0)
        prompts, budgets = traffic.job(c["traffic"], 0)
        job = play(loop, prompts, budgets)
        shapes = list(dict.fromkeys(tuple(s) for s in job["shapes"]))
        out.append({"workload": w["name"], "shapes": shapes,
                    "prompt_tokens": [len(prompt_ids(c["traffic"], p))
                                      for p in prompts],
                    "budgets": budgets, "stats": job["stats"]})
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--plan", action="store_true")
    ap.add_argument("--root", help="with --plan: plan the cells of the "
                    "benchmark at ROOT (a copy of BENCHMARK.json and "
                    "chipbench/ that holds a candidate cell)")
    args = ap.parse_args(argv)
    if args.root and not args.plan:
        ap.error("--root is read by --plan only")
    bench = spec.use_root(args.root) if args.root else spec.load_benchmark()
    if args.plan:
        for p in plan(bench):
            print(json.dumps(p), flush=True)
        return
    if not args.workload:
        ap.error("--workload is required")
    cell = spec.cell(bench, args.workload)
    result = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
