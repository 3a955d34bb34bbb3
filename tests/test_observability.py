"""Unified observability plane: lifecycle tracing, Prometheus-style
exposition, tick profiler, SLO flight recorder, and the metrics.py
edge cases the plane leans on.

Acceptance capstone: one rid's full span chain — enqueue -> admit ->
decode -> drain -> restore -> retire, across fault incarnations —
reconstructs from a flight-recorder dump via ``tools/tracedump.py``.
"""
import json
import math
import pathlib
import sys
import time

import pytest

from repro.configs.base import get_config
from repro.core.elastic import ElasticServing
from repro.core.jrm import SliceSpec, start_vk
from repro.core.metrics import (COUNT_BUCKETS, Endpoint, Histogram,
                                Registry, Service, split_series)
from repro.core.observability import (FlightRecorder, SLOConfig,
                                      TickProfiler, parse_exposition,
                                      render_exposition)
from repro.core.tracing import NULL_TRACER, Tracer
from repro.data.pipeline import Request, RequestSource
from repro.streaming.engine import StreamEngine
from repro.streaming.runtime import DecodeRuntime, RuntimeConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "tools"))
import metriclint                                             # noqa: E402
import tracedump                                              # noqa: E402


# ------------------------------------------------- metrics.py edge cases

def test_histogram_exact_boundary_lands_in_its_bucket():
    h = Histogram()                       # (0.005, 0.05, 0.5, ...)
    h.observe(0.05)                       # bisect_left: le=0.05 bucket
    assert h.counts[1] == 1 and sum(h.counts) == 1
    h.observe(0.005)
    assert h.counts[0] == 1
    h.observe(1e9)                        # +Inf bucket
    assert h.counts[-1] == 1


def test_histogram_quantile_empty_single_and_inf_mass():
    h = Histogram(buckets=(1.0, 2.0, math.inf))
    assert h.quantile(0.5) == 0.0         # empty -> 0.0
    h.observe(0.5)
    q = h.quantile(0.99)                  # single sample: inside (0, 1]
    assert 0.0 <= q <= 1.0
    h2 = Histogram(buckets=(1.0, math.inf))
    h2.observe(50.0)                      # all mass beyond the ladder
    assert h2.quantile(0.99) == 1.0       # largest finite bound
    h3 = Histogram(buckets=(1.0, 2.0, 4.0, math.inf))
    for v in (0.5, 1.5, 1.6, 3.0, 3.5):
        h3.observe(v)
    qs = [h3.quantile(q) for q in (0.1, 0.5, 0.9, 1.0)]
    assert qs == sorted(qs)               # monotone in q
    assert qs[-1] <= 4.0


def test_registry_labeled_series_are_distinct_and_stable():
    reg = Registry()
    reg.counter("ersap_shed_total", {"reason": "deadline"}).inc(3)
    reg.counter("ersap_shed_total", {"reason": "brownout"}).inc()
    reg.counter("ersap_shed_total", {"reason": "deadline"}).inc()
    assert reg.counter("ersap_shed_total",
                       {"reason": "deadline"}).value == 4
    # unlabeled API unchanged
    reg.gauge("ersap_queue_len").set(7)
    assert reg.metrics["ersap_queue_len"].value == 7
    base, lbl = split_series('ersap_shed_total{reason="deadline"}')
    assert base == "ersap_shed_total" and lbl == '{reason="deadline"}'
    # labeled histogram flattens with the label block preserved
    reg.histogram("ersap_queue_wait_s", {"tier": "lc"}).observe(0.2)
    flat = reg.collect()
    assert flat['ersap_queue_wait_s_sum{tier="lc"}'] == pytest.approx(0.2)
    assert flat['ersap_queue_wait_s_count{tier="lc"}'] == 1


def test_service_same_pod_ip_requires_unique_cp_ports():
    """§4.6.3: VK pods share VKUBELET_POD_IP, so endpoints must remap
    exporter ports to unique control-plane ports."""
    svc = Service("obs", selector={"app": "ersap"})
    svc.add_endpoint(Endpoint("p0", "10.0.0.1", 2221, 9100, Registry()))
    svc.add_endpoint(Endpoint("p1", "10.0.0.1", 2221, 9101, Registry()))
    with pytest.raises(ValueError):
        svc.add_endpoint(Endpoint("p2", "10.0.0.1", 2221, 9100,
                                  Registry()))
    assert len(svc.endpoints) == 2


# ------------------------------------------------------------ exposition

def test_exposition_renders_and_parses_back():
    reg = Registry()
    reg.counter("ersap_served_total").inc(5)
    reg.gauge("ersap_queue_len").set(3)
    h = reg.histogram("ersap_latency_s", buckets=(0.1, 1.0, math.inf))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(2.0)
    text = render_exposition({"pod-a": reg})
    assert "# TYPE ersap_latency_s histogram" in text
    assert "# TYPE ersap_served_total counter" in text
    flat = parse_exposition(text)
    assert flat['ersap_served_total{pod="pod-a"}'] == 5
    # bucket series are cumulative and end at +Inf == _count
    assert flat['ersap_latency_s_bucket{pod="pod-a",le="0.1"}'] == 1
    assert flat['ersap_latency_s_bucket{pod="pod-a",le="1"}'] == 2
    assert flat['ersap_latency_s_bucket{pod="pod-a",le="+Inf"}'] == 3
    assert flat['ersap_latency_s_count{pod="pod-a"}'] == 3
    assert flat['ersap_latency_s_sum{pod="pod-a"}'] == \
        pytest.approx(2.55)
    # the standalone metriclint parser agrees (no repro imports there)
    tmp = pathlib.Path(str(ROOT)) / "bench_check"
    tmp.mkdir(exist_ok=True)
    f = tmp / "_test_expo.prom"
    f.write_text(text)
    try:
        assert metriclint.parse_exposition_file(str(f)) == flat
    finally:
        f.unlink()


def test_exposition_parser_rejects_malformed_lines():
    with pytest.raises(ValueError):
        parse_exposition("ersap_x{unclosed 1")
    with pytest.raises(ValueError):
        parse_exposition("ersap_x notafloat")
    with pytest.raises(ValueError):
        parse_exposition("just-one-token")
    assert parse_exposition("# comment\n\n") == {}


# ---------------------------------------------------------------- tracer

def test_tracer_ring_bound_and_chain_order():
    tr = Tracer(cap=8)
    for i in range(12):
        tr.span("decode", float(i), rid=1, step=i)
    assert len(tr.spans) == 8 and tr.dropped == 4
    chain = tr.chain(1)
    assert [s.attrs["step"] for s in chain] == list(range(4, 12))
    assert [s.seq for s in chain] == sorted(s.seq for s in chain)


def test_tracer_incarnation_bumps_on_restore_and_block_spans_match():
    tr = Tracer()
    tr.span("enqueue", 0.0, rid=7)
    tr.span("prefill", 1.0, rids=(7, 9))      # block span, rid=0
    tr.span("restore", 2.0, rid=7)
    tr.span("decode", 3.0, rid=7)
    incs = [s.inc for s in tr.chain(7)]
    assert incs == [0, 0, 1, 1]               # restore itself is inc=1
    assert tr.rids() == [7, 9]
    assert NULL_TRACER.span("x", 0.0) is None and not NULL_TRACER.spans
    d = tr.dump()
    assert d[1]["attrs"]["rids"] == [7, 9]    # JSON-safe (tuple -> list)


def test_tracer_region_times_host_work_in_start_order():
    tr = Tracer()
    t0 = time.perf_counter()
    with tr.region("decode", 2.0, rids=(4,), steps=16) as sp:
        tr.span("admit", 2.0, rid=4)
        time.sleep(0.01)
        sp.attrs["finished"] = 1
    t1 = time.perf_counter()
    dec, adm = tr.spans
    assert (dec.name, adm.name) == ("decode", "admit")     # start order
    assert dec.seq < adm.seq and dec.t == 2.0
    assert t0 <= dec.wall <= adm.wall <= dec.end <= t1
    assert dec.dur >= 0.01 and adm.dur == 0.0
    assert tr.chain(4) == [dec, adm]
    d = tr.dump()[0]
    assert d["attrs"] == {"rids": [4], "steps": 16, "finished": 1}
    assert (d["wall"], d["dur"]) == (dec.wall, dec.dur)
    with NULL_TRACER.region("decode") as off:
        assert off is None
    assert not NULL_TRACER.spans


# ------------------------------------------------ runtime host regions

def test_runtime_regions_stamp_admission_first_token_and_live_rows():
    """A runtime with a tracer: each rid is admitted before its first
    token reaches the host, which is before ``step()`` returns; the fused
    block books the row-steps that deliver tokens against those the
    whole table computes."""
    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)
    rt = DecodeRuntime(serving.runtime_kernels(RuntimeConfig(
        max_batch=4, paged=True, page_size=16, admit_tail=0)),
        serving.params, record_tokens=True, tracer=Tracer())
    rt.submit([Request(1, 0.0, 8, 3), Request(2, 0.0, 12, 16)])
    rt.step()
    returned = time.perf_counter()
    tr = rt.tracer
    for rid in (1, 2):
        names = [s.name for s in tr.chain(rid)]
        assert names[:4] == ["admit", "prefill", "first_token", "decode"]
        adm, _, first, _ = tr.chain(rid)[:4]
        assert adm.wall <= first.end <= returned
        assert len(rt.token_log[rid]) >= 1
    dec = next(s for s in tr.spans if s.name == "decode")
    assert (dec.attrs["steps"], dec.attrs["rows"]) == (16, 5)
    assert dec.attrs["live_row_steps"] == 19             # 3 + 16 of 80
    inner = [s for s in tr.spans
             if dec.wall <= s.wall and s.end <= dec.end and s is not dec]
    assert {"decode_read", "harvest"} <= {s.name for s in inner}
    harvest = [s for s in inner if s.name == "harvest"]
    assert harvest[-1].attrs["finished"] == 2            # both retired
    names = {s.name for s in tr.spans}
    assert {"admit_plan", "pages_commit"} <= names
    assert all(s.dur > 0 for s in tr.spans if s.name != "admit")


def test_runtime_decode_region_counts_kernel_blocks(monkeypatch):
    """The ``decode`` region books the paged kernel's grid in one layer
    call at the block's last step: every row's ceil(kvb / block) grid
    steps, and each busy row's ceil(depth / block) live ones, with the
    block the kernel's own (here 2 pages of 16 = 32 entries)."""
    from repro.kernels import paged_decode_attention as PDA
    cfg = get_config("qwen2-7b").reduced()
    page_bytes = cfg.n_kv_heads * 16 * cfg.head_dim * cfg.jdtype.itemsize
    monkeypatch.setattr(PDA, "BLOCK_BYTES", 2 * page_bytes)
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)
    rt = DecodeRuntime(serving.runtime_kernels(RuntimeConfig(
        max_batch=4, paged=True, page_size=16, admit_tail=0)),
        serving.params, record_tokens=True, tracer=Tracer())
    # prompt buckets 8, 16, 64; decode starts at the bucket's depth
    rt.submit([Request(1, 0.0, 8, 3), Request(2, 0.0, 12, 40),
               Request(3, 0.0, 40, 30)])
    for _ in range(3):
        rt.step()
    got = [(s.attrs["steps"], s.attrs["kvb"], s.attrs["kv_blocks"],
            s.attrs["live_kv_blocks"])
           for s in rt.tracer.spans if s.name == "decode"]
    assert got == [
        # depths 8+3, 16+16, 64+16 -> 1 + 1 + 3 live; 5 rows x ceil(80/32)
        (16, 80, 15, 5),
        # depths 32+16, 80+14 -> 2 + 3; 5 rows x ceil(96/32)
        (16, 96, 15, 5),
        # depth 48+8 -> 2; 5 rows x ceil(64/32)
        (8, 64, 10, 2)]


# -------------------------------------------------------------- profiler

def test_tick_profiler_accumulates_and_nests():
    p = TickProfiler()
    with p.phase("tick.schedule"):
        with p.phase("pump.admit"):
            pass
    with p.phase("tick.schedule"):
        pass
    s = p.summary()
    assert s["tick.schedule"]["calls"] == 2
    assert s["pump.admit"]["calls"] == 1
    assert s["tick.schedule"]["total_s"] >= 0.0
    assert s["tick.schedule"]["mean_us"] >= 0.0


# ------------------------------------------------------- flight recorder

def test_flight_recorder_trips_slo_and_writes_incident(tmp_path):
    tr = Tracer()
    tr.span("enqueue", 0.0, rid=1)
    fr = FlightRecorder(tr, slo=SLOConfig(lc_p99_s=1.0, min_samples=4,
                                          cooldown_s=60.0),
                        dump_dir=str(tmp_path))
    for i in range(8):
        fr.note_latency(float(i), 5.0, priority=100)   # way over SLO
        fr.note_served(float(i))
    assert fr.check(8.0) is not None
    assert fr.check(9.0) is None                       # cooldown holds
    assert fr.check(120.0) is not None                 # cooldown expired
    files = sorted(tmp_path.glob("incident_*.json"))
    assert len(files) == 2
    bundle = json.loads(files[0].read_text())
    assert bundle["reason"] == "lc-p99"
    assert bundle["spans"] and bundle["spans"][0]["rid"] == 1
    assert bundle["burn"]["lc_p99_s"] == pytest.approx(5.0)
    # full dump is JSON-safe and tracedump-readable
    dump = json.loads(json.dumps(fr.dump()))
    assert tracedump.all_rids(tracedump.spans_of(dump)) == [1]
    assert [i["reason"] for i in dump["incidents"]] == \
        ["lc-p99", "lc-p99"]


def test_flight_recorder_shed_and_restore_burn():
    fr = FlightRecorder(slo=SLOConfig(shed_frac=0.25, restore_s=10.0,
                                      min_samples=2, window_s=100.0))
    for i in range(6):
        fr.note_served(float(i))
        fr.note_latency(float(i), 0.1)
    b = fr.burn(6.0)
    assert b["shed_frac"] == 0.0
    for i in range(6):
        fr.note_shed(float(i))
    assert fr.check(6.0)["reason"] == "shed-fraction"
    fr2 = FlightRecorder(slo=SLOConfig(restore_s=10.0))
    fr2.note_restore(5.0, 30.0)
    assert fr2.check(5.0)["reason"] == "restore-latency"
    # sliding window forgets old samples
    assert fr2.burn(5000.0)["restore_max_s"] == 0.0


def test_invariant_auditor_trips_recorder_before_raising():
    from types import SimpleNamespace

    from repro.core.chaos import ChaosInvariantError, InvariantAuditor
    from repro.core.cluster import Cluster
    cluster = Cluster()
    cluster.register_node(start_vk("n0", now=0.0,
                                   slice_spec=SliceSpec(chips=2)), 0.0)
    fr = FlightRecorder()
    dup = SimpleNamespace(runtimes={}, completed=[(7, 0.0), (7, 1.0)],
                          queue=[], _node_reachable=lambda name: True)
    aud = InvariantAuditor(cluster, engine=dup, recorder=fr)
    with pytest.raises(ChaosInvariantError):
        aud.audit(1.0)
    assert fr.incidents and fr.incidents[0]["reason"] == "invariant"


# ------------------------------------------------------ tracedump helpers

def test_tracedump_subsequence_and_render():
    assert tracedump.has_subsequence(
        ["enqueue", "admit", "decode", "decode", "retire"],
        ["enqueue", "decode", "retire"])
    assert not tracedump.has_subsequence(
        ["admit", "enqueue"], ["enqueue", "admit"])
    bundle = {"spans": [
        {"name": "enqueue", "t": 0.0, "rid": 3, "seq": 1, "inc": 0,
         "attrs": {}},
        {"name": "decode", "t": 1.0, "rid": 0, "seq": 2, "inc": 0,
         "attrs": {"rids": [3], "steps": 16}},
        {"name": "retire", "t": 2.0, "rid": 3, "seq": 3, "inc": 0,
         "attrs": {"tokens": 16}},
    ]}
    assert tracedump.find_chain(bundle, ["enqueue", "decode", "retire"]) \
        == 3
    assert tracedump.find_chain(bundle, ["enqueue", "restore"]) is None
    out = tracedump.render(bundle)
    assert "rid 3" in out and "retire" in out


def test_metriclint_inventory_is_clean():
    """Every ersap_* metric named anywhere in src/ must be documented in
    docs/ARCHITECTURE.md — the same gate the obs-smoke CI job runs."""
    assert metriclint.main([]) == 0


# -------------------------------------- capstone: end-to-end span chain

def _mk_engine(n_nodes=2, chips=2):
    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)
    nodes = [start_vk(f"n{i}", now=0.0, slice_spec=SliceSpec(chips=chips))
             for i in range(n_nodes)]
    return StreamEngine(cfg, serving, nodes, service_rate=100.0,
                        max_batch=4,
                        runtime_cfg=RuntimeConfig(max_batch=4,
                                                  admit_tail=0))


def test_request_chain_reconstructs_across_drain_restore(tmp_path):
    """Acceptance: a request is admitted, its node is drained mid-flight
    (checkpoint -> evict -> reschedule -> restore), and it finishes on
    the replacement replica. The flight-recorder dump reconstructs the
    whole life — enqueue, admit, decode, drain, restore, retire — for
    that one rid, across fault incarnations, via tools/tracedump.py."""
    eng = _mk_engine()
    tracer = Tracer()
    recorder = FlightRecorder(tracer, dump_dir=str(tmp_path / "inc"))
    eng.deploy(0.0)
    eng.enable_observability(tracer=tracer, recorder=recorder,
                             profiler=TickProfiler())
    eng.plane.nodes.ckpt_dir = str(tmp_path / "ckpt")
    eng.reconcile(0.0)
    assert eng.runtimes

    # one long request, hand-stamped the way RequestSource.arrivals does
    pod0 = next(iter(eng.runtimes))
    rt = eng.runtimes[pod0]
    tracer.span("enqueue", 0.0, rid=1, prompt_len=8, max_new=48)
    rt.sim_now = 0.0
    rt.submit([Request(1, 0.0, 8, 48, trace_id=1)], force=True)
    rt.step()                              # admit + one block: in flight
    assert any(s.busy for s in rt.slots)

    # drain the node under it; reconcile reschedules with restored state
    victim = eng.pods[pod0].node
    eng.plane.nodes._drain_node(victim, 1.0)
    eng.reconcile(1.0)
    assert any(p.node != victim for p in eng.pods.values())

    # replacement replica finishes the request
    for t in range(2, 8):
        eng.reconcile(float(t))
        eng.tick(float(t), 1.0, lam=0.0)
        if any(rid == 1 for rid, _ in eng.completed):
            break
    assert any(rid == 1 for rid, _ in eng.completed)

    out = tmp_path / "trace.json"
    out.write_text(json.dumps(recorder.dump()))
    bundle = json.loads(out.read_text())
    want = ["enqueue", "admit", "decode", "drain", "restore", "retire"]
    assert tracedump.find_chain(bundle, want) == 1
    # the same life is visible across fault incarnations: admits on both
    # sides of the restore carry different inc stamps
    names_incs = [(s["name"], s["inc"]) for s in
                  tracedump.rid_spans(tracedump.spans_of(bundle), 1)]
    admits = [inc for name, inc in names_incs if name == "admit"]
    assert 0 in admits and 1 in admits
    assert ("retire", 1) in names_incs
    # CLI gate used by the obs-smoke job
    assert tracedump.main([str(out), "--require-chain",
                           ",".join(want)]) == 0

    # the unified pipeline saw the request end to end
    flat = parse_exposition(eng.exposition())
    served = sum(v for k, v in flat.items()
                 if k.startswith("ersap_served_total"))
    assert served >= 1
    assert any(k.startswith("ersap_queue_wait_s_count") or
               k.startswith("ersap_ttft_s_count") for k in flat)


def test_engine_observability_is_opt_in_and_metrics_always_on():
    """Without enable_observability the engine runs span-free (the <5%%
    bench contrasts exactly this), while the unified registry still
    records shed/served counters for the compat properties."""
    src = RequestSource(seed=3)
    eng = _mk_engine()
    eng.deploy(0.0)
    eng.queue.extend(src.arrivals(0.0, 1.0, lam=6.0))
    eng.tick(0.0, 1.0, lam=0.0)
    assert eng.tracer is None and eng.recorder is None
    assert eng.total_served > 0
    assert isinstance(eng.shed_counts, dict)
    flat = parse_exposition(eng.exposition())
    served = sum(v for k, v in flat.items()
                 if k.startswith("ersap_served_total"))
    assert served == eng.total_served
    assert 'ersap_queue_len{pod="_engine"}' in flat   # engine registry
