"""Cluster registry: `make_cluster(name, config)` for every protocol/backend.

One construction path for apples-to-apples comparisons (S9): benchmarks,
examples, tests, and the serving/ckpt integrations all build clusters here,
so a new workload automatically runs against every protocol and a new
protocol automatically runs under every workload.

Registered names
----------------
  nezha              exact event-driven Nezha (proxied, S5)
  nezha-nonproxy     Nezha-Non-Proxy (proxy logic on the client, S9.7)
  nezha-vectorized   `VectorizedNezhaCluster` -- staged DOM engine
                     (numpy compute tier; pass VectorizedConfig(tier=...)
                     or use the tier-pinned names below)
  nezha-vectorized-jit      same engine, fused-jit DOM tier
  nezha-vectorized-pallas   same engine, Pallas dom_admit/dom_release
                            kernel tier (TPU; interpreted on the CPU)
  nezha-sharded      `ShardedNezhaCluster` -- G independent Nezha groups
                     over one key space (ShardedConfig(groups=...)); stable
                     key->group routing, cross-group multi-key ops in
                     global deadline order, optional vmapped group dispatch
  multipaxos, raft, fastpaxos, nopaxos, nopaxos-optim, domino,
  toq-epaxos, unreplicated          -- the S9/S10 baselines

Config promotion: pass the protocol's own config class, a bare
`CommonConfig` (shared fields are copied into the protocol's config), or
None (defaults). Extra keyword arguments are forwarded to the cluster
constructor (e.g. ``sm_factory=`` for Nezha backends, ``percentile=`` for
Domino).
"""
from __future__ import annotations

from dataclasses import dataclass, fields, replace
from typing import Callable, Optional

from repro.core.baselines import PROTOCOLS, BaselineConfig
from repro.core.cluster import Cluster, CommonConfig
from repro.core.protocol import ClusterConfig, NezhaCluster
from repro.core.sharded import ShardedConfig, ShardedNezhaCluster
from repro.core.vectorized_cluster import VectorizedConfig, VectorizedNezhaCluster


@dataclass(frozen=True)
class ClusterEntry:
    name: str
    config_cls: type
    factory: Callable[..., Cluster]


_REGISTRY: dict[str, ClusterEntry] = {}


def register_cluster(name: str, config_cls: type,
                     factory: Callable[..., Cluster]) -> None:
    if name in _REGISTRY:
        raise ValueError(f"cluster {name!r} already registered")
    _REGISTRY[name] = ClusterEntry(name, config_cls, factory)


def available_clusters() -> tuple[str, ...]:
    return tuple(_REGISTRY)


def config_class(name: str) -> type:
    """The config dataclass a registered cluster is constructed from --
    the scenario layer builds environment-specific configs against it."""
    entry = _REGISTRY.get(name)
    if entry is None:
        raise KeyError(
            f"unknown cluster {name!r}; available: {', '.join(_REGISTRY)}")
    return entry.config_cls


def _coerce_config(config: Optional[CommonConfig], config_cls: type):
    if config is None:
        return config_cls()
    if isinstance(config, config_cls):
        return config
    if isinstance(config, CommonConfig):
        # Promote: copy ONLY the CommonConfig-declared fields. This is how
        # one CommonConfig sweeps every protocol with identical fabric,
        # clocks, and client population. Protocol-specific fields (e.g. the
        # baselines' calibrated replica_cpu vs Nezha's) keep the target's
        # defaults even when a sibling config class happens to share a
        # field name -- cross-family promotion must not leak calibration.
        kw = {f.name: getattr(config, f.name) for f in fields(CommonConfig)}
        return config_cls(**kw)
    raise TypeError(
        f"expected {config_cls.__name__} or CommonConfig, got {type(config).__name__}")


def make_cluster(name: str, config: Optional[CommonConfig] = None, *,
                 scenario=None, **kw) -> Cluster:
    """Construct any registered cluster behind the unified `Cluster` API.

    ``scenario`` (a `repro.sim.scenario.Scenario` or cataloged name) is the
    declarative construction path: the config is built from the scenario's
    environment + overrides via `repro.sim.scenario.build_config`. Note this
    configures the cluster only -- `run_scenario` additionally schedules the
    scenario's fault events and drives its workload.
    """
    entry = _REGISTRY.get(name)
    if entry is None:
        raise KeyError(
            f"unknown cluster {name!r}; available: {', '.join(_REGISTRY)}")
    if scenario is not None:
        if config is not None:
            raise TypeError("pass either config or scenario, not both")
        from repro.sim.scenario import build_config

        config = build_config(name, scenario)
    return entry.factory(_coerce_config(config, entry.config_cls), **kw)


def _make_nonproxy(cfg: ClusterConfig, **kw) -> NezhaCluster:
    if not cfg.co_locate_proxies:
        cfg = replace(cfg, co_locate_proxies=True)
    return NezhaCluster(cfg, **kw)


def _make_vectorized_tier(tier: str) -> Callable[..., Cluster]:
    def factory(cfg: VectorizedConfig, **kw) -> VectorizedNezhaCluster:
        if cfg.tier != tier:
            cfg = replace(cfg, tier=tier)
        return VectorizedNezhaCluster(cfg, **kw)
    return factory


register_cluster("nezha", ClusterConfig, NezhaCluster)
register_cluster("nezha-nonproxy", ClusterConfig, _make_nonproxy)
register_cluster("nezha-vectorized", VectorizedConfig, VectorizedNezhaCluster)
register_cluster("nezha-vectorized-jit", VectorizedConfig,
                 _make_vectorized_tier("jit"))
register_cluster("nezha-vectorized-pallas", VectorizedConfig,
                 _make_vectorized_tier("pallas"))
register_cluster("nezha-sharded", ShardedConfig, ShardedNezhaCluster)
for _name, _cls in PROTOCOLS.items():
    register_cluster(_name, BaselineConfig, _cls)


__all__ = ["make_cluster", "register_cluster", "available_clusters",
           "config_class", "ClusterEntry"]
