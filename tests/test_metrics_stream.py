"""Monitoring stack (§4.6) + elastic serving + streaming engine."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.elastic import ElasticServing
from repro.core.jrm import SliceSpec, start_vk
from repro.core.metrics import (Endpoint, Prometheus, Registry, Service,
                                ServiceMonitor)
from repro.streaming.engine import StreamEngine


# ----------------------------------------------------------------- metrics

def test_registry_and_scrape():
    reg = Registry()
    reg.counter("served").inc(5)
    reg.gauge("queue").set(7)
    reg.histogram("lat").observe(0.3)
    svc = Service("s", selector={"app": "x"}, labels={"monitored": "true"})
    svc.add_endpoint(Endpoint("pod-0", "172.17.0.1", 2221, 20000, reg))
    prom = Prometheus(monitors=[ServiceMonitor("m", {"monitored": "true"})],
                      services=[svc])
    n = prom.scrape(now=1.0)
    assert n >= 3
    assert prom.query_latest("served")["pod-0"] == 5
    prom.scrape(now=2.0)
    assert len(prom.query_range("queue", "pod-0")) == 2


def test_same_pod_ip_requires_port_remap():
    """§4.6.3: identical pod IPs + identical CP ports must be rejected."""
    svc = Service("s", selector={})
    svc.add_endpoint(Endpoint("a", "172.17.0.1", 2221, 20000, Registry()))
    with pytest.raises(ValueError):
        svc.add_endpoint(Endpoint("b", "172.17.0.1", 2221, 20000, Registry()))
    # remapped CP port is fine even with the same pod IP
    svc.add_endpoint(Endpoint("b", "172.17.0.1", 2221, 20001, Registry()))
    assert len(svc.endpoints) == 2


def test_service_label_selection():
    svc = Service("s", selector={"app": "ersap"})
    assert svc.selects({"app": "ersap", "x": "y"})
    assert not svc.selects({"app": "other"})


# ------------------------------------------------------------------ elastic

def test_elastic_scale_preserves_outputs():
    cfg = get_config("qwen2-7b").reduced()
    s = ElasticServing(cfg, tp=1)
    s.build(1, seed=0)
    toks = np.random.default_rng(0).integers(0, cfg.vocab, (2, 16)).astype(np.int32)
    l1, _ = s.prefill_fn(s.params, toks)
    s.scale_to(1)           # no-op
    assert s.replicas == 1
    s2 = s.build(s.max_replicas())
    l2, _ = s.prefill_fn(s.params, toks)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=1e-4, rtol=1e-4)
    assert len(s.scale_events) >= 1


# ------------------------------------------------------------------ engine

def test_stream_engine_serves_and_scales():
    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)
    nodes = [start_vk(f"n{i}", now=0.0, slice_spec=SliceSpec(chips=4))
             for i in range(2)]
    eng = StreamEngine(cfg, serving, nodes, service_rate=2.0, max_batch=4)
    eng.deploy(0.0)
    assert len(eng.pods) == 1
    total_q = 0
    for t in range(6):
        q = eng.tick(t * 5.0, 5.0, lam=2.0)
        total_q += q
    served = sum(st.served for st in eng.stats.values())
    assert served > 0
    assert eng.completed
    # metrics flowed through the Prometheus stack
    assert eng.prom.query_latest("ersap_served_total")
    # control loop runs and keeps replica count within bounds
    desired = eng.control_step(30.0)
    assert 1 <= desired <= serving.max_replicas()


def test_engine_real_model_tokens():
    """The engine runs actual prefill+decode: token counts add up."""
    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)
    nodes = [start_vk("n0", now=0.0, slice_spec=SliceSpec(chips=4))]
    eng = StreamEngine(cfg, serving, nodes, service_rate=1.0, max_batch=2)
    eng.deploy(0.0)
    eng.queue.extend(eng.source.arrivals(0.0, 1.0, lam=3.0))
    eng.tick(1.0, 2.0, lam=0.0)
    st = eng.stats["ersap-0"]
    assert st.tokens == st.served * 16     # max_new defaults to 16
