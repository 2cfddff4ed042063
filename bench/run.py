"""Run one cell of the benchmark on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the weights on the device from the seed, builds the
``PipelineServer`` the cell's configuration describes, serves every
distinct prompt length of the window's traffic once and then bursts of
1, 2, ... requests, up to the lanes the cell's load file names on every
replica at once, so that every program the window runs is compiled. The
window then offers the cell's traffic open-loop for ``--seconds``; after
it the server drains with no new arrivals for at most the cell's
``drain_s``. Once the server is freed, a sample of the finished
requests, drawn from the seed, is run through the float32 reference
(:mod:`bench.reference`). ``correct`` says that no attempted request
failed and that every sampled served token lies within the
configuration's limit of the reference's best logit.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
part of the window and from the engine's step hooks), ``device``,
``breakdown`` (``--trace 1``) and, last, ``checks``: each compared
number beside its limit, which also end standard error. Without a TPU
whose peaks are in :mod:`bench.peaks`, or with fewer chips than the cell
asks for, the run prints no result and exits with code 3.
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
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[0] = str(_ROOT)  # import the benchmark as the package ``bench``
if str(_ROOT / "src") not in sys.path:
    sys.path.insert(1, str(_ROOT / "src"))

import numpy as np  # noqa: E402

from bench import stats  # noqa: E402
from bench.client_loop import PhaseClock, run_window  # noqa: E402
from bench.peaks import NoChip, peaks_for  # noqa: E402
from bench.spec import Cell, load_cell, load_metric  # noqa: E402
from bench.traffic import generate, rng_for, warmup_lengths  # noqa: E402
from bench.work import Widths, Work, token_work  # noqa: E402

# The traced interval runs from this share of the window to its end:
# stopping the profiler stalls the host while it writes the trace, so the
# stall falls after the window, into the drain.
TRACE_FROM = 0.5
WARMUP_TOKENS = 2  # a prefill and one decode per warm-up request
MAX_WARMUP_STEPS = 100_000


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program in it."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def device_info(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    if require_chip:
        peaks_for(d.platform, d.device_kind)
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return info


def program_model(config: dict):
    """The serving program's model, built to the configuration file."""
    from repro.configs import get_config
    from repro.models import build_model

    p = config["program"]
    cfg = dataclasses.replace(
        get_config(p["arch"]),
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=config["torch_dtype"],
        param_dtype=config["torch_dtype"],
        attn_impl=p["attn_impl"],
    )
    return build_model(cfg)


def build_server(cell: Cell, seed: int):
    import jax.numpy as jnp

    from bench.weights import served_params
    from repro.serving import PipelineServer

    c, dep = cell.config, cell.config["deployment"]
    model = program_model(c)
    w = Widths.from_config(c)
    return PipelineServer(
        model,
        served_params(w, seed, dep["n_groups"], bool(c["tie_word_embeddings"]),
                      jnp.dtype(c["torch_dtype"])),
        n_groups=dep["n_groups"],
        n_replicas=dep["n_replicas"],
        policy=dep["policy"],
        harvest_bounds=tuple(cell.mix.energy["harvest"]),
        max_len=dep["max_len"],
        max_batch=dep["max_batch"],
        paged=True,
        page_size=dep["page_size"],
        max_pages=dep["max_pages"],
        prefill_chunk=dep["prefill_chunk"],
        async_depth=dep["async_depth"],
        seed=0,  # the engine's own draws (harvest, routing ties), as deployed
    )


def warm_up(server, lengths, vocab: int, seed: int) -> int:
    """Serve one request of every length the window will send; returns
    the steps it took."""
    rng = rng_for(seed, 4)
    reqs = [
        server.submit(rng.integers(0, vocab, size=n).astype(np.int32), n_tokens=WARMUP_TOKENS)
        for n in lengths
    ]
    if any(r is None for r in reqs):
        raise RuntimeError("the server refused a warm-up request")
    for step in range(MAX_WARMUP_STEPS):
        if all(r.done or r.dropped for r in reqs):
            break
        server.step()
    if not all(r.done for r in reqs):
        raise RuntimeError("warm-up did not finish every request")
    return step


def warm_lanes(server, lanes: int, length: int, vocab: int, seed: int) -> int:
    """Serve bursts of 1, 2, ... ``n_replicas * lanes`` requests of one
    prompt length through ``submit`` and ``step``, so that every stage
    meets calls of every lane count up to ``lanes``, the most the cell's
    window brings to one (stage, replica); returns the steps it took."""
    return sum(warm_up(server, [length] * k, vocab, seed) for k in range(1, server.R * lanes + 1))


class CompileCounter:
    """Counts XLA compiles and persistent-cache loads by host time."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax

        self.times: dict[str, list[float]] = {self.COMPILE: [], self.LOAD: []}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, *_a, **_k):
        if name in self.times:
            self.times[name].append(time.perf_counter())

    def between(self, lo: float, hi: float) -> tuple[int, int]:
        """(compiles, cache loads) with host times in ``[lo, hi]``."""
        return tuple(sum(lo <= t <= hi for t in self.times[k]) for k in (self.COMPILE, self.LOAD))


# ---------------------------------------------------------------------------
# Reading the window
# ---------------------------------------------------------------------------


def end_to_end(clients, t0: float, end: float, seconds: float, setup_s: float) -> dict:
    ok = [c for c in clients if c.done and c.token_times]
    ttft = [c.token_times[0] - c.due for c in ok]
    itl = [
        (b - a) * 1e3
        for c in clients
        for a, b in zip(c.token_times, c.token_times[1:])
        if b <= end
    ]
    delivered = sum(t0 <= t <= end for c in clients for t in c.token_times)
    log(f"samples: ttft {len(ttft)} requests, itl {len(itl)} gaps, "
        f"tokens in window {delivered}")
    out = {"setup_s": setup_s, "tokens_per_s": delivered / seconds}
    if ttft:
        out["ttft_p50_s"] = stats.percentile(ttft, 50)
        out["ttft_p90_s"] = stats.percentile(ttft, 90)
    if itl:
        log("itl deciles ms: " + ", ".join(f"{stats.percentile(itl, q):.3f}" for q in range(10, 100, 10)))
        out["itl_mean_ms"] = sum(itl) / len(itl)
        out["itl_p50_ms"] = stats.percentile(itl, 50)
        out["itl_p90_ms"] = stats.percentile(itl, 90)
        out["itl_p95_ms"] = stats.percentile(itl, 95)
    log("end to end: " + ", ".join(f"{k} {v!r}" for k, v in out.items()))
    return out


def delivered_work(clients, lo: float, hi: float, w: Widths, chunk: int) -> Work:
    """The work of the tokens that reached clients in ``[lo, hi]``."""
    total = Work()
    for c in clients:
        for k, t in enumerate(c.token_times):
            if lo <= t <= hi:
                total = total + token_work(w, c.prompt_len, k, chunk)
    return total


def stats_delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before if isinstance(before[k], (int, float))}


class Occupancy:
    """How full the server is, sampled between steps: requests resident,
    lanes in use on a (stage, replica) and pages in use in its pool."""

    def __init__(self, server):
        self.managers = list(server.managers.values())
        self.lanes_max = server.max_batch
        self.pages_max = server.max_pages
        self.lanes: list[int] = []
        self.pages: list[int] = []

    def sample(self) -> None:
        self.lanes.append(max(m.n_slots - m.free_slots() for m in self.managers))
        self.pages.append(max(m.pool.used_pages for m in self.managers))

    def summary(self, clients, t0: float, end: float) -> str:
        if not self.lanes:
            return "occupancy in the window: not sampled"
        # Requests in the system, averaged over the window (due to last token).
        held = sum(max(0.0, min(c.token_times[-1] if c.done else end, end) - max(c.due, t0))
                   for c in clients)
        return (f"occupancy in the window: requests in the system mean {held / (end - t0):.3f}; "
                f"{len(self.lanes)} samples of the fullest (stage, replica): "
                f"lanes in use mean {np.mean(self.lanes):.3f}, peak {max(self.lanes)} of "
                f"{self.lanes_max}; pages in use mean {np.mean(self.pages):.1f}, peak "
                f"{max(self.pages)} of {self.pages_max}")


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices())


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------


def draw_sample(clients, seed: int, tokens: int):
    """Finished requests, drawn from the seed, the longest first, until
    they hold ``tokens`` served tokens."""
    done = [c for c in clients if c.done]
    if not done:
        return []
    longest = max(done, key=lambda c: c.prompt_len + c.n_out)
    rest = [c for c in done if c is not longest]
    order = rng_for(seed, 3).permutation(len(rest))
    sample, n = [longest], longest.n_out
    for i in order:
        if n >= tokens:
            break
        sample.append(rest[i])
        n += rest[i].n_out
    return [(np.asarray(c.req.prompt, np.int32), list(c.req.generated)) for c in sample]


def logit_gaps(config: dict, seed: int, sample, control: bool = False):
    """Per sampled stream, the gap at each served position between the
    reference's best logit and the reference's logit of the token served,
    or, with ``control``, of the token that the float8 control puts first
    at that position of the same prompt and served tokens: the control's
    tokens put in the place of the program's."""
    from bench.reference import reference_logits

    w = Widths.from_config(config)
    seqs = [np.concatenate([p, np.asarray(g[:-1], np.int32)]) for p, g in sample]
    rows = [np.arange(len(p) - 1, len(p) - 1 + len(g)) for p, g in sample]
    kw = dict(theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
              tied=bool(config["tie_word_embeddings"]))
    ref = reference_logits(w, seed, seqs, rows, **kw)
    if control:
        ctl = reference_logits(w, seed, seqs, rows, control=True, **kw)
        chosen = [c.argmax(axis=1) for c in ctl]
    else:
        chosen = [np.asarray(g) for _, g in sample]
    out = []
    for r, ch in zip(ref, chosen):
        if not np.isfinite(r).all():
            out.append(np.full(len(ch), np.inf))
            continue
        out.append(r.max(axis=1) - r[np.arange(len(ch)), ch])
    return out


def verdict(config: dict, failed: int, gaps) -> tuple[bool, dict]:
    """``correct`` and the numbers compared, each beside its limit: no
    attempted request failed, and no sampled token lies further below
    the reference's best logit than the configuration's limit."""
    limit = float(config["correct"]["max_logit_gap"])
    flat = np.concatenate(gaps) if len(gaps) else np.zeros(0)
    worst = float(flat.max()) if flat.size else float("inf")
    ok = failed == 0 and flat.size > 0 and np.isfinite(worst) and worst <= limit
    return bool(ok), {"failed": {"value": failed, "limit": 0},
                      "max_logit_gap": {"value": worst, "limit": limit}}


def drop_request_state(clients) -> None:
    """Let go of the device arrays finished requests still point at."""
    for c in clients:
        if c.req is not None:
            c.req.hidden = None
            c.req.chunk_outs = []
            c.req.chunk_seq = None


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, t_start: float = T_START,
             control: bool = False) -> dict:
    """Set up, measure, check; returns the result object (``checks`` last).

    ``control`` (``bench/control.py`` and its test only, never a
    benchmark run): the float8 control's tokens take the place of the
    served ones on the same sample, so ``correct`` and ``checks`` are the
    control's, judged as a run is; the program's own are under
    ``program``."""
    import jax

    from repro.serving import engine as engine_mod
    from repro.serving import readback

    device = device_info(cell.chips, require_chip)
    log(f"device: {device}")
    c, dep = cell.config, cell.config["deployment"]
    w = Widths.from_config(c)
    counter = CompileCounter()
    rate = float(cell.load["rate_per_s"])
    arrivals = generate(cell.mix, rate, seconds, seed, w.vocab)

    server = build_server(cell, seed)
    lengths = warmup_lengths(cell.mix, rate, seconds)
    lanes = min(int(cell.load.get("warm_lanes", dep["max_batch"])), dep["max_batch"])
    log(f"warm-up: {len(lengths)} lengths in {warm_up(server, lengths, w.vocab, seed)} steps, "
        f"lane bursts in {warm_lanes(server, lanes, lengths[0], w.vocab, seed)} steps")
    stats0 = dataclasses.asdict(server.stats)
    traces0 = sum(engine_mod.trace_counts().values())

    clock = PhaseClock(annotate=trace) if trace else None
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    traced = {}

    occupancy = Occupancy(server)

    def on_tick(now, t0):
        if now < t0 + seconds:
            occupancy.sample()
        if not trace:
            return
        if "start" not in traced and now >= t0 + TRACE_FROM * seconds:
            jax.profiler.start_trace(trace_dir)
            traced["start"] = time.perf_counter()
        elif "start" in traced and "stop" not in traced and now >= t0 + seconds:
            traced["stop"] = time.perf_counter()
            jax.profiler.stop_trace()

    if clock is not None:
        readback.set_observer(clock)
    try:
        clients, t0, end, n_steps = run_window(
            server, arrivals, seconds=seconds, drain_s=float(cell.load["drain_s"]),
            events=cell.mix.events, clock=clock, on_tick=on_tick,
        )
    finally:
        if clock is not None:
            readback.set_observer(None)
        if "start" in traced and "stop" not in traced:
            traced["stop"] = time.perf_counter()
            jax.profiler.stop_trace()
    t_done = time.perf_counter()
    setup_s = t0 - t_start
    delta = stats_delta(stats0, dataclasses.asdict(server.stats))
    compiles = counter.between(t0, end)
    retraces = sum(engine_mod.trace_counts().values()) - traces0
    attempted = len(clients)
    failed = sum(cl.failed for cl in clients)
    lags = [cl.lag_s for cl in clients]
    log(f"window {seconds} s from t0, drain ended {t_done - end:.3f} s after it; "
        f"attempted {attempted}, failed {failed} (refused {sum(cl.refused for cl in clients)})")
    log(f"generator lag: max {max(lags) * 1e3:.3f} ms, p99 {stats.percentile(lags, 99) * 1e3:.3f} ms")
    log(f"compiles in the window: {compiles[0]}, compile-cache loads {compiles[1]} "
        f"(engine retraces {retraces}); "
        f"{n_steps} engine steps in it, {1e3 * seconds / max(n_steps, 1):.3f} ms each")
    log(occupancy.summary(clients, t0, end))
    device["memory_peak_bytes"] = memory_peak_bytes() if require_chip else 0

    result = {"correct": False, "attempted": attempted, "failed": failed}
    breakdown = None
    if not trace:
        metrics = end_to_end(clients, t0, end, seconds, setup_s)
        result["metrics"] = {
            m["name"]: {"value": metrics[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in metrics
        }
    else:
        from bench.peaks import PEAKS
        from bench.trace_reduce import find_xplane, read_trace

        tr = read_trace(find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        lo, hi = traced.get("start", t0), traced.get("stop", end)
        # What a reader of bench/metrics/ may read.
        ctx = types.SimpleNamespace(
            trace=tr, peaks=PEAKS.get(device["kind"]),
            steps=[s for s in clock.steps if len(s) == 4 and t0 <= s[0] < end],
            stats=delta, attempted=attempted,
            work=delivered_work(clients, lo, hi, w, dep["prefill_chunk"]),
        )
        result["metrics"] = {}
        for m in cell.per_layer:
            value = load_metric(m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
        breakdown = {"device_ops": tr.top_ops(10), "idle_gaps": tr.idle_gaps(10)}
        log(f"trace: busy {device['busy_s']:.6f} s of {device['window_s']:.6f} s, "
            f"{len(tr.device)} device events, {len(tr.host)} host spans")
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown

    # The reference runs once the server's memory is free and the peak read.
    sample = draw_sample(clients, seed, int(c["correct"]["sample_tokens"]))
    drop_request_state(clients)
    del server
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    gaps = logit_gaps(c, seed, sample) if sample else []
    log(f"reference: {len(sample)} streams, {sum(len(g) for g in gaps)} served tokens, "
        f"{time.perf_counter() - t_ref:.1f} s")
    correct, checks = verdict(c, failed, gaps)
    if control:
        # The control in the program's place, judged by the same verdict.
        result["program"] = {"correct": correct, "checks": checks}
        correct, checks = verdict(c, failed, logit_gaps(c, seed, sample, control=True)
                                  if sample else [])
    result["correct"], result["checks"] = correct, checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    log(f"compile cache: {use_compile_cache()}")
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"no result: {e}")
        return 3
    for name, chk in result["checks"].items():
        log(f"check {name}: {chk['value']!r} (limit {chk['limit']!r})")
    log(f"correct: {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
