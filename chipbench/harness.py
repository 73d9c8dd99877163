"""One run of one cell: set-up, warm-up, the measured window, the
check against the plain reference, and the result line.

The window drives the program's normal entry point,
``repro.core.run_method``, with the trainer that
``repro.fl.client.build_fl_clients`` builds and the benchmark's own
wireless network, merging through the Pallas fedagg kernel as a
server on the chip does.  The federation (initial weights, data,
partition, delays, selection draws) is the configuration's
``federation_seed``: the weights steer CSTT's cohort sizes through the
accuracy, so weights drawn per seed gave each seed other work.
``--seed`` draws the rounds and clients the check compares.  The runners have no stop
hook, so the trainer is wrapped in a proxy (``RoundRecorder``) that

* hands the runner the weights the benchmark made;
* records what each ``local_train_batch`` call took in and gave back
  (the ids, the data-stream seed, the start model, the trained rows),
  and counts the distinct clients trained (padded slots repeat the
  last id);
* stamps a round boundary at every ``evaluate``: the runners evaluate
  once per round after the merge and read the accuracy back to the
  host, so every round ends with the device idle;
* opens the window after ``OPEN_AFTER_ROUNDS`` rounds, closes it at the
  first round boundary past ``--seconds`` and ends the run there.
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import data, spec
from chipbench import reference as ref

CAPTURE_ROUNDS = 3        # rounds of the window compared with the reference
REF_CLIENTS = 8           # clients trained by the reference per run
TRACE_SECONDS = 6.0       # traced part at the window's end
OPEN_AFTER_ROUNDS = 3     # rounds before the window opens
END_TO_END = ("setup_s", "updates_per_s", "round_s", "round_p90_s")


class StopWindow(Exception):
    """Raised from ``evaluate`` once the window has closed."""


class SetupError(RuntimeError):
    """The cell cannot be run as its files state."""


@dataclasses.dataclass
class Call:
    ids: List[int]
    seed: int               # the round's data-stream seed
    start: object           # params pytree all clients start from
    out: object             # stacked trained rows


@dataclasses.dataclass
class Capture:
    index: int              # round number, counted from the run's start
    g_in: object
    calls: List[Call]
    g_out: object


class RoundRecorder:
    """Trainer proxy: see the module docstring."""

    def __init__(self, trainer, weights, *, seconds: float, seed: int,
                 trace_dir: Optional[str] = None):
        self._t = trainer
        self._weights = weights
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.n_rounds = 0
        self.calls: List[Call] = []
        self.t_prev = None
        self.t_open = self.t_close = None
        self.t_trace = None               # the traced part's start
        self.n_untraced = 0               # window rounds before it
        self.round_times: List[float] = []
        self.round_updates: List[int] = []
        self.host_spans: List[tuple] = []
        self.captures: List[Capture] = []
        self._eligible = 0
        self._rng = np.random.default_rng([seed, 0xC4B7])
        self.mark = None
        self.last_params = weights

    def __getattr__(self, name):
        return getattr(self._t, name)

    def init_params(self, seed: int = 0):
        return self._weights

    @staticmethod
    def distinct(ids) -> int:
        return len(set(int(c) for c in ids))

    def _span(self, name, t0):
        if self.t_open is not None:
            self.host_spans.append((name, t0, time.perf_counter()))

    def local_train_batch(self, params, client_ids, rnd_seed, *, wrap=None):
        t0 = time.perf_counter()
        kw = {} if wrap is None else {"wrap": wrap}
        stacked, sizes = self._t.local_train_batch(params, client_ids,
                                                   rnd_seed, **kw)
        self.calls.append(Call([int(c) for c in client_ids], int(rnd_seed),
                               params, stacked))
        self._span("trainer.local_train_batch", t0)
        return stacked, sizes

    def evaluate(self, params):
        t0 = time.perf_counter()
        acc = self._t.evaluate(params)
        now = time.perf_counter()
        self._span("trainer.evaluate", t0)
        calls, self.calls = self.calls, []
        updates = sum(self.distinct(c.ids) for c in calls)
        self.n_rounds += 1
        g_in, self.last_params = self.last_params, params
        if self.t_open is not None:
            self.round_times.append(now - self.t_prev)
            self.round_updates.append(updates)
            if updates:
                self._maybe_capture(Capture(self.n_rounds, g_in, calls,
                                            params))
            if (self.trace_dir is not None and self.t_trace is None
                    and now - self.t_open >= self.seconds - TRACE_SECONDS):
                self._start_trace()
                now = self.t_trace            # the start is no round's time
        if self.t_open is None and self.n_rounds == OPEN_AFTER_ROUNDS:
            self._open()
            now = time.perf_counter()
        elif self.t_open is not None and now - self.t_open >= self.seconds:
            self.t_close = now
            raise StopWindow()
        self.t_prev = now
        return acc

    def _open(self):
        self.t_open = time.perf_counter()
        if self.trace_dir is not None and self.seconds <= TRACE_SECONDS:
            self._start_trace()
            self.t_open = self.t_trace

    def _start_trace(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # keep the host's Python unslowed
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.clock"):
            pass
        self.mark = t
        self.n_untraced = len(self.round_times)
        self.t_trace = time.perf_counter()

    def _maybe_capture(self, cap: Capture):
        # reservoir sample of the window's rounds, drawn from the seed
        j = self._eligible
        self._eligible += 1
        if j < CAPTURE_ROUNDS:
            self.captures.append(cap)
        else:
            r = int(self._rng.integers(0, j + 1))
            if r < CAPTURE_ROUNDS:
                self.captures[r] = cap


# -- set-up ---------------------------------------------------------------

def fl_config(cfg: dict, rounds: int = 10 ** 9):
    from repro.config.base import FLConfig
    fed = dict(cfg["federation"])
    for k in ("tier_delay_means", "failure_delay"):
        fed[k] = tuple(float(v) for v in fed[k])
    return FLConfig(rounds=rounds, seed=cfg["federation_seed"], **fed)


def make_weights(cfg: dict, seed: int):
    """The cell's initial global model, on the device, in one jitted
    call from the seed."""
    import jax
    model = ref.model_module(cfg["model"])
    sizes = cfg["sizes"]

    def init(lo, hi):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                                 hi)
        return model.init(sizes, key)

    return jax.jit(init)(np.uint32(seed & 0xFFFFFFFF),
                         np.uint32((seed >> 32) & 0xFFFFFFFF))


def check_trainer(cfg: dict, trainer, weights):
    """Fail the run where the program's trainer does not hold the work
    the configuration's file states."""
    import jax
    n = cfg["federation"]["n_clients"]
    sizes = sorted({len(c) for c in trainer.clients})
    if len(trainer.clients) != n or sizes != [cfg["samples_per_client"]]:
        raise SetupError(
            f"{cfg['name']}: the trainer built {len(trainer.clients)} clients "
            f"with sample counts {sizes[:8]}; the configuration states {n} x "
            f"{cfg['samples_per_client']}")
    steps = (cfg["samples_per_client"] // cfg["federation"]["batch_size"]
             * cfg["federation"]["local_epochs"])
    if steps != cfg["local_steps"]:
        raise SetupError(f"{cfg['name']}: {steps} local steps, the "
                         f"configuration states {cfg['local_steps']}")
    want = jax.eval_shape(trainer.init_params, 0)
    got = jax.tree_util.tree_structure(weights)
    if jax.tree_util.tree_structure(want) != got or [
            (l.shape, l.dtype) for l in jax.tree_util.tree_leaves(want)] != [
            (l.shape, l.dtype) for l in jax.tree_util.tree_leaves(weights)]:
        raise SetupError(f"{cfg['name']}: the program's model "
                         f"{cfg['arch']} and the reference's differ in layout")
    leaves = jax.tree_util.tree_leaves(weights)
    n_params = sum(int(np.prod(l.shape)) for l in leaves)
    if (n_params, len(leaves)) != (cfg["n_params"], cfg["n_leaves"]):
        raise SetupError(f"{cfg['name']}: {n_params} params in {len(leaves)} "
                         f"leaves, the configuration states "
                         f"{cfg['n_params']} in {cfg['n_leaves']}")


def warm_up(cfg: dict, trainer, params):
    """Run every program the window can dispatch, at every shape the
    cell's traffic can give it, through the calls the runner makes: a
    round trains its survivors (at most tau from each tier) in a pow2
    bucket and merges as many rows as survived."""
    import jax

    from repro.core.engine import make_engine

    fed = cfg["federation"]
    top = min(fed["tau"] * fed["n_tiers"], fed["n_clients"])
    eng = make_engine(trainer, use_kernel_agg=True)
    by_bucket: Dict[int, List[int]] = {}
    for n in range(1, top + 1):
        by_bucket.setdefault(eng._pad_target(n), []).append(n)
    ids = [c % fed["n_clients"] for c in range(max(by_bucket))]
    out = [trainer.evaluate(params)]
    for b, ns in sorted(by_bucket.items()):
        stacked, sizes = eng._local_train_batch(params, ids[:b], 10 ** 6)
        for n in ns:
            rows = (stacked if n == b else
                    jax.tree_util.tree_map(lambda l, n=n: l[:n], stacked))
            out.append(eng.aggregate_or_keep(params, rows, sizes[:n]))
    jax.block_until_ready(out)


# -- the check ------------------------------------------------------------

@dataclasses.dataclass
class Entry:
    """One real client of a captured round, as the reference sees it."""
    client: int
    seed: int               # data-stream seed
    start: List[np.ndarray]  # the model it trained from
    row: List[np.ndarray]   # the program's trained model
    weight: float           # its sample count, from the benchmark's data


def round_inputs(cap: Capture, samples) -> List[Entry]:
    """The real clients of a captured round, in merge order."""
    entries = []
    for call in cap.calls:
        start = ref.leaves64(call.start)
        for c, pos in ref.unique_in_order(call.ids).items():
            entries.append(Entry(c, call.seed, start,
                                 ref.row_of(call.out, pos),
                                 float(len(samples[c][1]))))
    return entries


def compare(cfg: dict, trainer, captures: List[Capture], seed: int,
            stand_in=None, precision: Optional[str] = None,
            detail: Optional[list] = None) -> Dict[str, float]:
    """The numbers ``correct`` is decided by (``reference`` docstring),
    over the captured rounds.  ``stand_in`` (calibration: the control
    and the planted faults) maps a capture's round to ``{"rows":
    [trained model per entry], "g_out": merged global}`` put in the
    program's place; ``detail`` (calibration) receives one line per
    compared client."""
    import jax
    import jax.numpy as jnp
    model = ref.model_module(cfg["model"])
    sizes_cfg, fed = cfg["sizes"], cfg["federation"]
    rng = np.random.default_rng([seed, 0x5EED])
    samples = data.clients(cfg)
    merge_gaps, train_gaps = [], []
    treedef = jax.tree_util.tree_structure(captures[0].g_in)
    as_tree = lambda leaves: jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(a, jnp.float32) for a in leaves])

    work = []
    for cap in captures:
        entries = round_inputs(cap, samples)
        alt = stand_in.get(cap.index) if stand_in else None
        if alt is not None:
            for e, row in zip(entries, alt["rows"]):
                e.row = row
        got = alt["g_out"] if alt is not None else ref.leaves64(cap.g_out)
        want = ref.sync_merge([e.row for e in entries],
                              [e.weight for e in entries])
        merge_gaps.append(ref.merge_gap(got, want))
        work += [(cap.index, e) for e in entries]

    # clients whose start model fits their first batch exactly (a zero
    # gradient in f32) train to no change on either side: nothing to
    # compare, so the compared clients are drawn from the others
    grads = ref.make_grad_norms(model, sizes_cfg)
    moving = []
    for r, e in work:
        xs, ys = ref.client_stream(*samples[e.client], fed["batch_size"],
                                   fed["local_epochs"], e.seed)
        g = [float(n) for n in grads(as_tree(e.start), jnp.asarray(xs[0]),
                                     jnp.asarray(ys[0]))]
        if max(g) > 0:
            moving.append((r, e, g))
    todo = [moving[i] for i in
            sorted(rng.permutation(len(moving))[:REF_CLIENTS])]
    if todo:
        train = ref.make_train(model, sizes_cfg, fed["lr"],
                               precision=ref.precision_of(
                                   precision or cfg["matmul_precision"]))
        streams = [ref.client_stream(*samples[e.client], fed["batch_size"],
                                     fed["local_epochs"], e.seed)
                   for _, e, _ in todo]
        pad = [todo[-1]] * (REF_CLIENTS - len(todo))     # one program shape
        pad_s = [streams[-1]] * len(pad)
        starts = jax.tree_util.tree_unflatten(treedef, [
            jnp.asarray(np.stack([e.start[j] for _, e, _ in todo + pad]),
                        jnp.float32) for j in range(len(todo[0][1].start))])
        trained = train(starts,
                        jnp.asarray(np.stack([x for x, _ in streams + pad_s])),
                        jnp.asarray(np.stack([y for _, y in streams + pad_s])))
        for k, (r, e, g) in enumerate(todo):
            gap, leaf = ref.train_gap(e.start, e.row, ref.row_of(trained, k),
                                      ref.kept_leaves(g))
            train_gaps.append(gap)
            if detail is not None:
                detail.append({"round": r, "client": e.client, "gap": gap,
                               "leaf": leaf, "grad_norm": float(np.sum(
                                   np.square(g)) ** 0.5)})
    nums = {"data_gap": float(data.data_gap(trainer.clients, samples)),
            "merge_gap": ref.worst(merge_gaps)}
    if train_gaps:
        nums["train_gap_median"] = float(np.median(train_gaps))
    return nums



def finite(tree) -> bool:
    import jax
    return all(bool(np.isfinite(np.asarray(l)).all())
               for l in jax.tree_util.tree_leaves(tree))


# -- per-layer metrics ----------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets: the profiler traces the
    window's last ``TRACE_SECONDS``, and ``rounds``, ``window_s`` and
    ``round_updates`` are those of that traced part."""
    cell: dict
    rounds: int
    window_s: float
    round_updates: List[int]
    spans: List[tuple]            # (name, t0_ns, t1_ns) on the trace clock
    devices: List[dict]           # chipbench.trace.reduce_device output
    peaks: dict
    chips: int
    n_params: int


def read_trace(rec: RoundRecorder, tel) -> tuple:
    """-> (device reductions, the spans that start in the traced part,
    in trace ns)."""
    from chipbench import trace
    path = trace.find_xplane(rec.trace_dir)
    pd = trace.load(path)
    mark_ns, _ = trace.find_host_event(pd, "chipbench.clock")
    to_ns = lambda t: int(mark_ns + (t - rec.mark) * 1e9)
    t0, t1 = to_ns(rec.t_trace), to_ns(rec.t_close)
    devices = [trace.reduce_device(p, t0, t1)
               for p in trace.device_planes(pd)]
    spans = [(n, to_ns(a), to_ns(b)) for n, a, b in rec.host_spans]
    if tel is not None:
        for s in tel.spans:
            a = tel.t0 + s["ts_us"] / 1e6
            spans.append((s["name"], to_ns(a), to_ns(a + s["dur_us"] / 1e6)))
    return devices, [sp for sp in spans if t0 <= sp[1] < t1]


# -- one run --------------------------------------------------------------

def run(cell_name: str, seed: int, seconds: float, traced: bool,
        t_process: float, log, *, cell: Optional[dict] = None,
        require_tpu: bool = True, trace_dir: Optional[str] = None,
        keep: Optional[dict] = None) -> dict:
    """One run of ``cell_name``; returns the result line's object.
    ``keep`` (calibration) receives the trainer, the captured rounds
    and the updates of each round."""
    import jax

    from repro import obs
    from repro.core import run_method
    from repro.fl.client import build_fl_clients

    from chipbench.network import WirelessNetwork

    cell = cell or spec.cell(cell_name)
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < cell["chips"]):
        raise SetupError(f"cell {cell_name} needs {cell['chips']} TPU "
                         f"chip(s); found {len(devs)} {devs[0].platform} "
                         f"device(s) ({devs[0].device_kind})")
    unknown = [m["name"] for m in cell["end_to_end"]
               if m["name"] not in END_TO_END]
    if unknown:
        raise SetupError(f"no end-to-end metric named {unknown}")
    compiles = {"n": 0, "in_window": 0, "names": []}
    fl = fl_config(cfg)
    trainer = build_fl_clients(cfg["arch"], fl, dataset=cfg["dataset"],
                               scale=cfg["data_scale"])
    weights = make_weights(cfg, cfg["federation_seed"])
    check_trainer(cfg, trainer, weights)
    warm_up(cfg, trainer, weights)
    log(f"[setup] {cfg['name']} {traffic['name']}: {len(trainer.clients)} "
        f"clients x {cfg['samples_per_client']} samples, warm-up done at "
        f"{time.perf_counter() - t_process!r} s")

    fed = cfg["federation"]
    net = WirelessNetwork(fed["n_clients"], fed["tier_delay_means"],
                          fed["delay_std"], fed["mu"],
                          tuple(fed["failure_delay"]), cfg["federation_seed"])
    rec = RoundRecorder(trainer, weights, seconds=seconds, seed=seed,
                        trace_dir=trace_dir if traced else None)
    _listen_compiles(compiles, rec)
    tel = None
    error = None
    try:
        if traced:
            with obs.tracing() as tel:
                run_method(traffic["method"], rec, net, fl,
                           use_kernel_agg=True)
        else:
            run_method(traffic["method"], rec, net, fl, use_kernel_agg=True)
    except StopWindow:
        pass
    except Exception as e:            # noqa: BLE001 -- reported as failed
        import traceback
        log(traceback.format_exc())
        error = f"{type(e).__name__}: {e}"
    if traced and rec.t_trace is not None:
        jax.profiler.stop_trace()
    setup_s = (rec.t_open - t_process) if rec.t_open else float("nan")
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs[:max(cell["chips"], 1)])

    closed = rec.t_close is not None and error is None
    # a round that raised trained the clients of its pending calls
    pending = sum(rec.distinct(c.ids) for c in rec.calls)
    attempted = int(sum(rec.round_updates)) + pending
    final_ok = closed and finite(rec.last_params)
    failed = 0 if final_ok else attempted
    w_s = (rec.t_close - rec.t_open) if closed else float("nan")
    n_rounds = len(rec.round_times)
    log(f"[window] {n_rounds} rounds, {attempted} client updates in "
        f"{w_s!r} s; compiles in the window: {compiles['in_window']} "
        f"{compiles['names'][:5]}; run error: {error}")

    out = {"correct": False, "attempted": attempted, "failed": failed,
           "metrics": {}, "device": device_info(devs, cell, mem)}
    if closed:
        if traced:
            out["metrics"], out["breakdown"], busy = per_layer(
                cell, rec, tel, devs, cfg)
            out["device"]["busy_s"] = busy
            out["device"]["window_s"] = rec.t_close - rec.t_trace
        else:
            values = {"setup_s": setup_s,
                      "updates_per_s": attempted / w_s,
                      "round_s": w_s / n_rounds,
                      "round_p90_s": float(np.percentile(rec.round_times,
                                                         90))}
            out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                          "unit": m["unit"]}
                              for m in cell["end_to_end"]}
        log(f"[rounds] {n_rounds} rounds, mean {w_s / n_rounds!r} s, median "
            f"{statistics.median(rec.round_times)!r} s, p90 "
            f"{float(np.percentile(rec.round_times, 90))!r} s; updates per "
            f"round {np.bincount(rec.round_updates).tolist()}")
    # the reference runs with the program's run over and its state freed
    captures = rec.captures
    rec.last_params = None
    nums = (compare(cfg, trainer, captures, seed)
            if closed and captures else {})
    if keep is not None:
        keep.update(trainer=trainer, captures=captures, nums=nums,
                    round_updates=list(rec.round_updates))
    check = {}
    ok = closed and final_ok and failed == 0 and bool(nums)
    for name, limit in cell["limits"]["limits"].items():
        if name not in nums:
            continue
        check[name] = {"value": nums[name], "limit": limit}
        ok = ok and nums[name] <= limit
    if compiles["in_window"]:
        log(f"[compiles] {compiles['in_window']} in the window: "
            f"{compiles['names']}")
    out["correct"] = bool(ok)
    out["check"] = check
    return out


def _listen_compiles(compiles: dict, rec: RoundRecorder):
    from jax import monitoring

    def on_duration(event, duration, **kw):
        if event.endswith("backend_compile_duration") or (
                "compilation_cache" in event and "retrieval" in event):
            compiles["n"] += 1
            if rec.t_open is not None and rec.t_close is None:
                compiles["in_window"] += 1
                compiles["names"].append(event.rsplit("/", 1)[-1])

    monitoring.register_event_duration_secs_listener(on_duration)


def device_info(devs, cell, mem) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": min(len(devs), cell["chips"]),
            "memory_peak_bytes": int(mem)}


def per_layer(cell, rec, tel, devs, cfg):
    devices, spans = read_trace(rec, tel)
    chips = cell["chips"]
    devices = devices[:chips]
    n = rec.n_untraced
    ctx = Context(cell=cell, rounds=len(rec.round_times) - n,
                  window_s=rec.t_close - rec.t_trace,
                  round_updates=list(rec.round_updates[n:]),
                  spans=spans, devices=devices,
                  peaks=spec.peaks(devs[0].device_kind), chips=chips,
                  n_params=cfg["n_params"])
    metrics = {}
    for m in cell["per_layer"]:
        value = spec.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    from chipbench import trace
    breakdown = {"device_ops": [], "idle_gaps": []}
    busy = 0.0
    if devices:
        busiest = max(devices, key=lambda d: d["busy_ns"])
        top = sorted(busiest["ops_ns"].items(), key=lambda kv: -kv[1])[:10]
        breakdown = {"device_ops": [[n, ns / 1e9] for n, ns in top],
                     "idle_gaps": trace.label_gaps(busiest["gaps"], spans)}
        busy = sum(d["busy_ns"] for d in devices) / len(devices) / 1e9
    return metrics, breakdown, busy
