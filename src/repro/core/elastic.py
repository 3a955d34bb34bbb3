"""Elastic data-parallel scaling (HPA/twin decision -> new mesh).

A serving deployment is R replicas x TP chips. Scaling re-builds the mesh
as (R', TP), re-lowers prefill/decode, and reshards params onto the new
topology (device_put from the current mesh onto the new one).

Compiled artifacts are cached per (replicas, tp): scaling back to a
previously-seen size reuses the mesh, the jitted prefill/decode closures
(so jax's own trace cache keeps hitting — re-lowering was the dominant
scale-up cost), and the serving-runtime kernel set. The decode closure
donates its cache argument, so the per-token KV update is in-place
instead of a full slab copy per step."""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax

from repro.configs.base import ArchConfig
from repro.launch.mesh import make_mesh
from repro.models import model_api as MA
from repro.sharding.api import ShardCtx, tree_shardings


@dataclass
class ElasticServing:
    cfg: ArchConfig
    tp: int = 1
    replicas: int = 0
    mesh: object = None
    ctx: Optional[ShardCtx] = None
    params: object = None
    prefill_fn: object = None
    decode_fn: object = None
    scale_events: list = field(default_factory=list)
    build_gen: int = 0                     # bumped on every (re)build
    # (replicas, tp) -> (mesh, ctx, prefill_fn, decode_fn, param_shardings)
    _compiled: Dict[Tuple[int, int], tuple] = field(default_factory=dict)
    _kernels: Dict[tuple, object] = field(default_factory=dict)

    def max_replicas(self) -> int:
        n = len(jax.devices())
        if self.tp > n:
            raise ValueError(f"tp={self.tp} needs {self.tp} devices per "
                             f"replica; the {jax.default_backend()} "
                             f"platform has {n}")
        return n // self.tp

    def _lowered(self, replicas: int):
        key = (replicas, self.tp)
        if key in self._compiled:
            return self._compiled[key]
        mesh = make_mesh((replicas, self.tp), ("data", "model"))
        ctx = ShardCtx(mesh)
        mod = MA.get_module(self.cfg)
        aparams = mod.abstract_params(self.cfg)
        psh = tree_shardings(ctx, aparams, mod.param_axes(self.cfg))
        cfgl = self.cfg

        def prefill(params, tokens):
            return mod.prefill(params, tokens, cfgl, ctx)

        def decode(params, token, cache):
            return mod.decode_step(params, token, cache, cfgl, ctx)

        entry = (mesh, ctx, jax.jit(prefill),
                 jax.jit(decode, donate_argnums=(2,)), psh)
        self._compiled[key] = entry
        return entry

    def build(self, replicas: int, now: float = 0.0,
              seed: Optional[int] = None):
        """(Re)build at ``replicas`` data-parallel replicas. Parameters are
        drawn on the devices from ``seed`` (each device builds only its own
        shard), or else are the current ones, moved onto the new mesh."""
        replicas = min(max(replicas, 1), self.max_replicas())
        mesh, ctx, prefill_fn, decode_fn, psh = self._lowered(replicas)
        if seed is not None:
            mod = MA.get_module(self.cfg)
            init = functools.partial(mod.init, cfg=self.cfg)
            params = jax.jit(init, out_shardings=psh)(
                jax.random.PRNGKey(seed))
        elif self.params is not None:
            params = jax.tree.map(jax.device_put, self.params, psh)
        else:
            raise RuntimeError("no params yet — build with a seed")
        self.prefill_fn, self.decode_fn = prefill_fn, decode_fn
        old = self.replicas
        self.mesh, self.ctx, self.params = mesh, ctx, params
        self.replicas = replicas
        self.build_gen += 1
        if old != replicas:
            self.scale_events.append((now, old, replicas))
        return self

    def runtime_kernels(self, rcfg):
        """Serving-runtime kernel set for the *current* topology, cached per
        (replicas, tp, rcfg) so re-scaling to a seen size skips re-tracing."""
        from repro.streaming.runtime import RuntimeKernels
        key = (self.replicas, self.tp, rcfg)
        if key not in self._kernels:
            self._kernels[key] = RuntimeKernels(self.cfg, rcfg, self.ctx)
        return self._kernels[key]

    def scale_to(self, replicas: int, now: float = 0.0):
        replicas = min(max(replicas, 1), self.max_replicas())
        if replicas == self.replicas:
            return self
        return self.build(replicas, now=now)
