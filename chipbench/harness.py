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
``--seed`` draws the rounds and clients the check compares.  The cell's
traffic file (``traffic/<name>.json``) names the runner (``method``),
its keyword arguments (``runner``), the ``FLConfig`` fields it sets
(``federation``), the reference merge (``merge``) and, where it is
not the runner's own, its warm-up (``warm_up``: ``warmups/<name>.py``,
by default ``warmups/<method>.py``); the configuration's
``data`` and ``model`` name the modules under ``datasets/`` and
``models/`` that the check reads.  The runners have
no stop hook, so the trainer is wrapped in a proxy (``RoundRecorder``)
that

* hands the runner the weights the benchmark made;
* records what each call of the engine's entry points,
  ``local_train_batch`` and ``local_train_cohort``, took in and gave
  back (the ids, the per-row data-stream seeds, the start models, the
  trained rows), and counts the distinct clients trained (padded slots
  repeat the last id);
* where the traffic merges by staleness, learns from the benchmark's
  network (``SelectionWatch``) in which round each client was
  selected, and from that each merged client's staleness and the global
  model it should have started from;
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
REF_BYTES = 2 ** 32       # the reference's Adam state per program
TRACE_SECONDS = 6.0       # traced part at the window's end
OPEN_AFTER_ROUNDS = 3     # rounds before the window opens
END_TO_END = ("setup_s", "updates_per_s", "round_s", "round_p90_s")


class StopWindow(Exception):
    """Raised from ``evaluate`` once the window has closed."""


class SetupError(RuntimeError):
    """The cell cannot be run as its files state."""


@dataclasses.dataclass
class Call:
    """One call of an engine entry point: ``start`` is the model every
    row starts from (``shared``) or the stacked per-row starts."""
    ids: List[int]
    seeds: List[int]        # per-row data-stream seeds
    start: object
    out: object             # stacked trained rows
    shared: bool = True


@dataclasses.dataclass
class Selection:
    """A merged client as its selection left it (tracked traffic)."""
    staleness: Optional[int]   # None: no selection of it was seen
    at: object                 # global model at its selection round's start
    before: object             # the one a round earlier (None in round 1)


@dataclasses.dataclass
class Capture:
    index: int              # round number, counted from the run's start
    g_in: object
    calls: List[Call]
    g_out: object
    merged: List[Selection] = dataclasses.field(default_factory=list)


class RoundRecorder:
    """Trainer proxy: see the module docstring."""

    def __init__(self, trainer, weights, *, seconds: float, seed: int,
                 trace_dir: Optional[str] = None, track: bool = False):
        self._t = trainer
        self._weights = weights
        # selection tracking (``track``): V, the merged updates seen
        # before the current round, and per round in which clients still
        # in flight were selected, V and the global at its start.  The
        # globals are the runner's own arrays, held by reference: the
        # merges return new arrays and the store donates only its row
        # buffers, so nothing here is copied or read inside the window.
        self.track = track
        self.version = 0
        self.round_pos = 0
        self.selected: Dict[int, int] = {}      # client -> round
        self.v_start: Dict[int, int] = {}
        self.g_start: Dict[int, tuple] = {}     # round -> (at, before)
        self.prev_start = None
        self.round_merged: List[Selection] = []
        self.staleness: List[int] = []
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
        self._record(Call([int(c) for c in client_ids],
                          [int(rnd_seed)] * len(client_ids), params,
                          stacked))
        self._span("trainer.local_train_batch", t0)
        return stacked, sizes

    def local_train_cohort(self, start_params, client_ids, rnd_seeds, *,
                           wrap=None):
        t0 = time.perf_counter()
        kw = {} if wrap is None else {"wrap": wrap}
        stacked, sizes = self._t.local_train_cohort(start_params, client_ids,
                                                    rnd_seeds, **kw)
        self._record(Call([int(c) for c in client_ids],
                          [int(s) for s in rnd_seeds], start_params, stacked,
                          shared=False))
        self._span("trainer.local_train_cohort", t0)
        return stacked, sizes

    def _record(self, call: Call):
        self.calls.append(call)
        if not self.track:
            return
        for c in ref.unique_in_order(call.ids):
            r = self.selected.pop(c, None)
            if r is None:
                self.round_merged.append(Selection(None, None, None))
            else:
                s = self.version + self.round_pos - self.v_start[r]
                self.staleness.append(s)
                self.round_merged.append(Selection(s, *self.g_start[r]))
            self.round_pos += 1

    def selected_now(self, clients):
        """``clients`` were selected in the current round (called by
        ``SelectionWatch``)."""
        r = self.n_rounds + 1
        if r not in self.v_start:
            self.v_start[r] = self.version
            self.g_start[r] = (self.last_params, self.prev_start)
        for c in clients:
            self.selected[int(c)] = r

    def evaluate(self, params):
        t0 = time.perf_counter()
        acc = self._t.evaluate(params)
        now = time.perf_counter()
        self._span("trainer.evaluate", t0)
        calls, self.calls = self.calls, []
        merged, self.round_merged = self.round_merged, []
        updates = sum(self.distinct(c.ids) for c in calls)
        self.n_rounds += 1
        g_in, self.last_params = self.last_params, params
        if self.track:
            self._next_round(updates, g_in)
        if self.t_open is not None:
            self.round_times.append(now - self.t_prev)
            self.round_updates.append(updates)
            if updates:
                self._maybe_capture(Capture(self.n_rounds, g_in, calls,
                                            params, merged))
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

    def _next_round(self, updates: int, g_in):
        self.version += updates
        self.round_pos = 0
        self.prev_start = g_in
        live = set(self.selected.values())
        for r in [r for r in self.v_start if r not in live]:
            del self.v_start[r], self.g_start[r]

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


class SelectionWatch:
    """The benchmark's network, telling the recorder which clients each
    round selects: the runners draw a client's delay of attempt 0 at its
    selection (profiling draws are attempts 1 and up)."""

    def __init__(self, network, rec: RoundRecorder):
        self._net = network
        self._rec = rec

    def __getattr__(self, name):
        return getattr(self._net, name)

    def delays(self, clients, rnd, attempt=0):
        out = self._net.delays(clients, rnd, attempt)
        if not np.any(np.asarray(attempt)):
            self._rec.selected_now(np.atleast_1d(np.asarray(clients)))
        return out


# -- set-up ---------------------------------------------------------------

def fl_config(cfg: dict, rounds: int = 10 ** 9,
              traffic: Optional[dict] = None):
    """The program's ``FLConfig``: the configuration's federation and the
    fields the traffic sets."""
    from repro.config.base import FLConfig
    fed = dict(cfg["federation"])
    for k in ("tier_delay_means", "failure_delay"):
        fed[k] = tuple(float(v) for v in fed[k])
    fed.update((traffic or {}).get("federation", {}))
    return FLConfig(rounds=rounds, seed=cfg["federation_seed"], **fed)


def tracks_selection(traffic: dict) -> bool:
    """Clients train from a snapshot taken at their selection, merged by
    staleness: the recorder follows selections."""
    return traffic.get("merge", "sync_mean") == "staleness_fold"


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
    fault = data.module(cfg).check(cfg, trainer)
    if fault:
        raise SetupError(f"{cfg['name']}: {fault}")
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


def warm_up_module(traffic: dict):
    """The traffic's warm-up, ``warmups/<name>.py``: the one its
    ``warm_up`` key names, or else the one named as its runner."""
    name = traffic.get("warm_up", traffic["method"])
    try:
        return spec.warm_up(name)
    except ModuleNotFoundError as e:
        if e.name != f"chipbench.warmups.{name}":
            raise
        raise SetupError(f"no warm-up for {traffic['method']}: chipbench/"
                         f"warmups/{name}.py is missing, the window would "
                         f"compile")


def runner_kwargs(traffic: dict) -> dict:
    """The runner's keyword arguments: the traffic's ``runner``, or what
    its warm-up's ``runner_kwargs(traffic)`` builds from it (objects
    such as a mesh, which a JSON file cannot hold)."""
    build = getattr(warm_up_module(traffic), "runner_kwargs", None)
    return build(traffic) if build else dict(traffic.get("runner", {}))


def warm_up(cfg: dict, trainer, params, traffic: dict):
    """Run every program the window can dispatch, at every shape the
    cell's traffic can give it: the traffic's warm-up, which has to
    cover every keyword argument the traffic passes the runner."""
    mod = warm_up_module(traffic)
    other = sorted(set(traffic.get("runner", {})) - set(mod.RUNNER_KEYS))
    if other:
        raise SetupError(f"the warm-up of {traffic['method']} does not cover "
                         f"the runner keywords {other}: the window would "
                         f"compile")
    mod.warm(cfg, traffic, trainer, params)


# -- the check ------------------------------------------------------------

@dataclasses.dataclass
class Entry:
    """One real client of a captured round, as the reference sees it."""
    client: int
    seed: int               # data-stream seed
    start: List[np.ndarray]  # the model it trained from
    row: List[np.ndarray]   # the program's trained model
    weight: float           # its sample count, from the benchmark's data
    selection: Optional[Selection] = None   # tracked traffic only


def round_inputs(cap: Capture, ds, samples) -> List[Entry]:
    """The real clients of a captured round, in merge order; ``ds`` is
    the configuration's data module, ``samples`` its ``clients``."""
    import jax
    entries = []
    merged = iter(cap.merged)
    for call in cap.calls:
        if call.shared:
            start = ref.leaves64(call.start)
            start_of = lambda pos, start=start: start
        else:
            host = [np.asarray(l)
                    for l in jax.tree_util.tree_leaves(call.start)]
            start_of = lambda pos, host=host: [np.asarray(h[pos], np.float64)
                                               for h in host]
        for c, pos in ref.unique_in_order(call.ids).items():
            entries.append(Entry(c, call.seeds[pos], start_of(pos),
                                 ref.row_of(call.out, pos),
                                 float(ds.n_samples(samples[c])),
                                 next(merged, None)))
    return entries


def ref_block(cfg: dict) -> int:
    """Clients the reference trains per program: as many of
    ``REF_CLIENTS`` as fit ``REF_BYTES`` of Adam state (parameters,
    gradients and two moments, 16 bytes a parameter a client)."""
    return max(1, min(REF_CLIENTS, REF_BYTES // (16 * cfg["n_params"])))


def start_gap(entries: List[Entry]) -> int:
    """Merged clients whose start is not bitwise the global model at
    their selection round's start (or whose selection was not seen).
    Both sides are float64 widenings, which keep every bit of the
    narrower float, so their bits are compared."""
    bits = lambda a: a.view(np.int64)
    bad = 0
    for e in entries:
        sel = e.selection
        bad += sel is None or sel.at is None or not all(
            np.array_equal(bits(a), bits(b))
            for a, b in zip(e.start, ref.leaves64(sel.at)))
    return bad


def compare(cfg: dict, trainer, captures: List[Capture], seed: int,
            stand_in=None, precision: Optional[str] = None,
            detail: Optional[list] = None,
            traffic: Optional[dict] = None) -> Dict[str, float]:
    """The numbers ``correct`` is decided by (``reference`` docstring),
    over the captured rounds, for the traffic's merge (``sync_mean``
    where none is given).  ``stand_in`` (calibration: the control and
    the planted faults) maps a capture's round to ``{"rows": [trained
    model per entry], "g_out": merged global}`` and optionally
    ``"starts"`` put in the program's place; ``detail`` (calibration)
    receives one line per compared client."""
    import jax
    import jax.numpy as jnp
    model = ref.model_module(cfg["model"])
    ds = data.module(cfg)
    traffic = traffic or {}
    kind = traffic.get("merge", "sync_mean")
    fed = {**cfg["federation"], **traffic.get("federation", {})}
    sizes_cfg = cfg["sizes"]
    rng = np.random.default_rng([seed, 0x5EED])
    samples = ds.clients(cfg)
    merge_gaps, train_gaps, start_gaps = [], [], []
    treedef = jax.tree_util.tree_structure(captures[0].g_in)
    as_tree = lambda leaves: jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(a, jnp.float32) for a in leaves])

    work = []
    for cap in captures:
        entries = round_inputs(cap, ds, samples)
        alt = stand_in.get(cap.index) if stand_in else None
        if alt is not None:
            for e, row in zip(entries, alt["rows"]):
                e.row = row
            for e, st in zip(entries, alt.get("starts", [])):
                e.start = st
        got = alt["g_out"] if alt is not None else ref.leaves64(cap.g_out)
        g_in = ref.leaves64(cap.g_in) if kind == "staleness_fold" else None
        # a client whose selection was not seen counts in start_gap;
        # its merge weight is taken as fresh
        alphas = (ref.staleness_alphas(fed, [
            (e.selection.staleness if e.selection else None) or 0
            for e in entries]) if kind == "staleness_fold" else None)
        want = ref.merge(kind, g_in, [e.row for e in entries],
                         [e.weight for e in entries], alphas)
        merge_gaps.append(ref.merge_gap(got, want))
        if cap.merged:
            start_gaps.append(start_gap(entries))
        work += [(cap.index, e) for e in entries]

    # clients whose start model fits their first batch exactly (a zero
    # gradient in f32) train to no change on either side: nothing to
    # compare, so the compared clients are drawn from the others
    grads = ref.make_grad_norms(model, sizes_cfg)
    moving = []
    for r, e in work:
        xs, ys = ds.client_stream(cfg, samples[e.client], e.seed)
        g = [float(n) for n in grads(as_tree(e.start), jnp.asarray(xs[0]),
                                     jnp.asarray(ys[0]))]
        if max(g) > 0:
            moving.append((r, e, g))
    todo = [moving[i] for i in
            sorted(rng.permutation(len(moving))[:REF_CLIENTS])]
    block = ref_block(cfg)
    if todo:
        train = ref.make_train(model, sizes_cfg, fed["lr"],
                               precision=ref.precision_of(
                                   precision or cfg["matmul_precision"]))
    for i in range(0, len(todo), block):
        chunk = todo[i:i + block]
        streams = [ds.client_stream(cfg, samples[e.client], e.seed)
                   for _, e, _ in chunk]
        pad = [chunk[-1]] * (block - len(chunk))     # one program shape
        pad_s = [streams[-1]] * len(pad)
        starts = jax.tree_util.tree_unflatten(treedef, [
            jnp.asarray(np.stack([e.start[j] for _, e, _ in chunk + pad]),
                        jnp.float32) for j in range(len(chunk[0][1].start))])
        trained = train(starts,
                        jnp.asarray(np.stack([x for x, _ in streams + pad_s])),
                        jnp.asarray(np.stack([y for _, y in streams + pad_s])))
        for k, (r, e, g) in enumerate(chunk):
            gap, leaf = ref.train_gap(e.start, e.row, ref.row_of(trained, k),
                                      ref.kept_leaves(g))
            train_gaps.append(gap)
            if detail is not None:
                detail.append({"round": r, "client": e.client, "gap": gap,
                               "leaf": leaf, "grad_norm": float(np.sum(
                                   np.square(g)) ** 0.5)})
    nums = {"data_gap": float(ds.data_gap(trainer, samples)),
            "merge_gap": ref.worst(merge_gaps)}
    if train_gaps:
        nums["train_gap_median"] = float(np.median(train_gaps))
    if start_gaps:
        nums["start_gap"] = float(sum(start_gaps))
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
    ``keep`` (calibration) receives the trainer, the captured rounds,
    the updates of each round, the staleness of every merged client
    (tracked traffic) and, in a traced run, the program's telemetry."""
    import jax

    from repro import obs
    from repro.core import run_method

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
    fl = fl_config(cfg, traffic=traffic)
    trainer = data.module(cfg).build_trainer(cfg, fl)
    weights = make_weights(cfg, cfg["federation_seed"])
    check_trainer(cfg, trainer, weights)
    warm_up(cfg, trainer, weights, traffic)
    fed = cfg["federation"]
    log(f"[setup] {cfg['name']} {traffic['name']}: {fed['n_clients']} "
        f"clients, warm-up done at {time.perf_counter() - t_process!r} s")

    track = tracks_selection(traffic)
    rec = RoundRecorder(trainer, weights, seconds=seconds, seed=seed,
                        trace_dir=trace_dir if traced else None, track=track)
    net = WirelessNetwork(fed["n_clients"], fed["tier_delay_means"],
                          fed["delay_std"], fed["mu"],
                          tuple(fed["failure_delay"]), cfg["federation_seed"])
    if track:
        net = SelectionWatch(net, rec)
    runner_kw = runner_kwargs(traffic)
    _listen_compiles(compiles, rec)
    tel = None
    error = None
    try:
        if traced:
            with obs.tracing() as tel:
                run_method(traffic["method"], rec, net, fl, **runner_kw)
        else:
            run_method(traffic["method"], rec, net, fl, **runner_kw)
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
    rec.last_params = rec.prev_start = None
    rec.g_start.clear()
    nums = (compare(cfg, trainer, captures, seed, traffic=traffic)
            if closed and captures else {})
    if keep is not None:
        keep.update(trainer=trainer, captures=captures, nums=nums,
                    round_updates=list(rec.round_updates),
                    staleness=list(rec.staleness), telemetry=tel)
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
