"""After the window: gather what the run served, compare it with the plain
reference, and reduce the trace to per-layer metrics.

Numbers compared, each against its limit in ``limits.json``, and then any
that the configuration's truth module computes itself (``checks``), each
against the limit the configuration gives it:

* ``unanswered``: requests due in the window with no 200 answer by the end
  of the wait;
* ``topk_d2_err`` and ``topk_set_gap``: the index's top-k distances and
  neighbour sets for a sample of records drawn from the seed, against
  float64 distances from the index's embeddings;
* ``proxy_err`` and ``top1_err``: every numeric and top-1 proxy the engine
  served, against the float64 reference propagation from the
  representatives' true scores;
* ``answer_gap``: every answer, against the reference's replay of its spec
  over the served proxies with true labels;
* ``fresh_gap``: fresh labels summed over the answers, and the labels the
  oracle handed out, each against the number of distinct records the
  replays label.

A truth module that declares ``SERVED`` gets what the oracle handed out,
by id (:class:`mount.ServedOutputs`), as the last argument of its
``truth_of`` and ``checks``.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field

import numpy as np

from benchmarks.chip import devtrace, idle, latency, manifest, mount, reference

#: records whose top-k the check recomputes
TOPK_SAMPLE = 4096


@dataclass
class TopkSample:
    """A sample of the index's rows, drawn from the seed: the records'
    embeddings, the representatives' embeddings, and the stored top-k."""
    rows: np.ndarray
    x: np.ndarray
    reps: np.ndarray
    topk_ids: np.ndarray
    topk_d2: np.ndarray


def topk_sample(index, seed: int) -> TopkSample:
    n = index.n_records
    rows = np.sort(np.random.default_rng([seed, 5]).choice(
        n, min(TOPK_SAMPLE, n), replace=False))
    emb = index.embeddings
    return TopkSample(rows=rows, x=np.asarray(emb[rows]),
                      reps=np.asarray(emb[np.asarray(index.rep_ids)]),
                      topk_ids=np.asarray(index.topk_ids)[rows],
                      topk_d2=np.asarray(index.topk_d2)[rows])


def topk_errs(sample: TopkSample, control: bool = False) -> dict:
    """``topk_d2_err`` and ``topk_set_gap`` of the index's top-k, or of the
    float8 ``control`` computed from the same embeddings in its place."""
    ids, d2 = sample.topk_ids, sample.topk_d2
    if control:
        ids, d2 = reference.topk_fp8(sample.x, sample.reps, ids.shape[1])
    return reference.topk_gaps(sample.x, sample.reps, ids, d2)


@dataclass
class Loaded:
    """What a run served, held on the host after the window."""
    due: list                    # due offset of each request, by index
    specs: list                  # each request's spec
    rows: dict                   # request index -> load generator record
    wait_end: float              # end of the wait, as an offset
    t0: float                    # window start, perf_counter seconds
    proxies: dict                # (score, mode) -> served proxy
    rep_ids: np.ndarray
    topk_ids: np.ndarray
    topk_d2: np.ndarray
    truth: object                # score -> (ids -> true scores)
    topk: TopkSample = None
    traces: list = field(default_factory=list)
    k: int = 0
    oracle_labels: int = 0       # labels the oracle handed out since set-up
    own_checks: object = None    # () -> the configuration's own numbers

    @property
    def n_unanswered(self) -> int:
        return sum(not latency.answered(self.rows.get(i))
                   or self.rows[i]["done"] > self.wait_end
                   for i in range(len(self.due)))


def collect(server, engine, corpus, result: dict, plan: dict, args, mix,
            cfg, truth_mod, t0: float, oracle_labels: int,
            served: mount.ServedOutputs = None) -> Loaded:
    rows = {r["index"]: r for r in result["rows"]}
    due = [d for d, _ in plan["requests"]]
    specs = [body["specs"][0] for _, body in plan["requests"]]
    index = engine.index
    proxies = {pair: np.asarray(engine.proxy_scores(*pair), np.float64)
               for pair in mount.proxy_modes(mix)}
    traces = [t.to_dict() for t in server.obs.recorder.traces()]
    # today's truth modules take three arguments; one that declares
    # ``SERVED`` also takes what the oracle handed out
    outputs = None if served is None else served.outputs
    tail = () if served is None else (outputs,)
    own = getattr(truth_mod, "checks", None)
    return Loaded(
        due=due, specs=specs, rows=rows, wait_end=args.seconds + plan[
            "grace_s"], t0=t0, proxies=proxies,
        rep_ids=np.asarray(index.rep_ids), topk_ids=np.asarray(index.topk_ids),
        topk_d2=np.asarray(index.topk_d2),
        truth=lambda score: truth_mod.truth_of(corpus, cfg, score, *tail),
        topk=topk_sample(index, args.seed),
        traces=traces, k=index.k, oracle_labels=oracle_labels,
        own_checks=None if own is None else (
            lambda: own(corpus, cfg, outputs, args.seed)))


def proxy_errs(loaded: Loaded, control=None) -> dict:
    """``proxy_err`` over the numeric proxies and ``top1_err`` over the
    top-1 ones, against the float64 reference: of the served proxies, or
    of the ``control`` propagation put in their place."""
    worst = {"numeric": 0.0, "top1": 0.0}
    for (score, mode), served in loaded.proxies.items():
        rep_scores = loaded.truth(score)(loaded.rep_ids)
        ref = reference.propagate(rep_scores, loaded.topk_ids,
                                  loaded.topk_d2, mode)
        if control is not None:
            served = control(rep_scores, loaded.topk_ids, loaded.topk_d2,
                             mode)
        err = (reference.proxy_err(served, ref) if mode == "numeric"
               else reference.top1_err(served, ref, rep_scores))
        worst[mode] = max(worst[mode], err)
    return {"proxy_err": worst["numeric"], "top1_err": worst["top1"]}


def compare(loaded: Loaded, limits: dict) -> dict:
    values = {"unanswered": float(loaded.n_unanswered)}

    values.update(topk_errs(loaded.topk))
    values.update(proxy_errs(loaded))

    proxies = dict(loaded.proxies)
    for (score, mode), p in loaded.proxies.items():
        if mode == "numeric":
            proxies[(score, "order")] = reference.stratified_order(p)
    replays = {}
    labeled = set()
    gap, fresh = 0.0, 0
    for i, row in loaded.rows.items():
        if not latency.answered(row):
            continue
        spec = loaded.specs[i]
        key = json.dumps(spec, sort_keys=True)
        if key not in replays:
            replays[key] = reference.replay(spec, proxies, loaded.truth)
        ref, ids = replays[key]
        labeled.update(np.asarray(ids).tolist())
        served = row["body"]["results"][0]
        gap = max(gap, reference.answer_gap(spec, served, ref))
        fresh += row["body"]["request"]["fresh"]
    values["answer_gap"] = gap
    values["fresh_gap"] = float(max(abs(fresh - len(labeled)),
                                    abs(loaded.oracle_labels - len(labeled))))
    if loaded.own_checks is not None:
        values.update(loaded.own_checks())
    return judged(values, limits)


def judged(values: dict, limits: dict) -> dict:
    """Each number beside its limit; refuses a number with no limit and a
    limit with no number."""
    if set(values) != set(limits):
        raise manifest.ManifestError(
            f"numbers {sorted(set(values) - set(limits))} have no limit, "
            f"limits {sorted(set(limits) - set(values))} no number")
    return {name: {"value": v, "limit": limits[name]}
            for name, v in values.items()}


def layer_metrics(loaded: Loaded, readers: dict, layer: list, trace_dir,
                  sync: float, compiles: int, peaks: dict, base):
    """(per-layer metrics, {"device": busy and window, "breakdown": ...});
    prints the ``[idle]`` line (:mod:`idle`).  Readers get, among the rest,
    every kernel's counts by file stem and the trace file's path."""
    path = devtrace.find_xplane(trace_dir)
    red = devtrace.Reduction(path)
    offset = sync - red.lo
    requests = []
    for i in range(len(loaded.due)):
        row = loaded.rows.get(i)
        if row is None:
            continue
        requests.append({"trace_id": f"r{i}",
                         "fired": loaded.t0 + row["fired"],
                         "done": loaded.t0 + row["done"],
                         "answered": latency.answered(row)})
    calls = []
    for trace in loaded.traces:
        for s in trace["spans"]:
            a = s["attrs"]
            if s["name"] == "proxy.materialize" and a.get("source") == "device":
                calls.append({"records": a["n"], "k": loaded.k,
                              "reps": len(loaded.rep_ids), "mode": a["mode"]})
    ctx = {"traces": loaded.traces, "requests": requests,
           "n_requests": len(loaded.due), "compiles": compiles,
           "device": red, "propagate_calls": calls, "peaks": peaks,
           "kernels": manifest.kernels(base), "xplane": path}
    metrics = {}
    for m in layer:
        value = readers[m["name"]].read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    _, acct = idle.idle_in_request(red, path)
    print(idle.report_line(acct, red.window_s), flush=True)
    spans = [(s["t0"], s["t1"], s["name"]) for t in loaded.traces
             for s in t["spans"] if s["t1"] is not None]
    extra = {"device": {"busy_s": red.busy_s(), "window_s": red.window_s},
             "breakdown": {"device_ops": red.top_ops(10),
                           "idle_gaps": devtrace.label_gaps(
                               red.idle_gaps(), offset, spans)}}
    return metrics, extra
