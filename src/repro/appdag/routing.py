"""Seeded node-limited MoE router: who sends how many tokens where.

An expert-parallel all-to-all moves what the router decided, so its
legs are as uneven as the routing.  ``route`` draws one layer's routing
for an EP group and returns the counts a plan emitter needs:

* ``node_tokens[r, n]``: rank ``r``'s tokens that reach node ``n``,
  each token counted once per node however many of its experts sit
  there (DeepSeek-V3 sends a token over InfiniBand once per target node
  and forwards it over NVLink inside the node);
* ``pairs[r]``: the (token, expert) pairs rank ``r``'s experts receive,
  which sets the rank's expert compute.

The router is DeepSeek-V3's (arXiv:2412.19437 §2.1.2, §2.1.2's
node-limited routing): affinity ``sigmoid(z + b_e)`` with ``z ~ N(0, 1)``
per (token, expert) and an expert bias ``b_e ~ N(0, bias_sigma)`` drawn
per call, standing for the imbalance that auxiliary-loss-free balancing
leaves in one microbatch; experts fall into ``cfg.n_expert_groups``
groups; a token keeps the ``cfg.groups_per_token`` groups with the
largest sum of their two highest affinities, then its
``cfg.experts_per_token`` highest-affinity experts inside them.  Experts
are placed in order, ``n_experts / ranks`` to a rank, so a node holds
whole groups and a token reaches at most ``groups_per_token`` nodes
(DeepSeek-V3 has a group a node).  ``sample`` tokens a rank are drawn
and every count is scaled to ``tokens``.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.configs.base import ModelConfig


@dataclass(frozen=True)
class RouteStats:
    """What one layer's routing asks of the fabric and the experts.

    ``pairs_*``: routed (token, expert) pairs a rank receives.
    ``ib_bytes_*``: bytes a port sends plus receives over the
    inter-node fabric in the layer's forward dispatch (FP8 tokens, one
    fp32 scale per 128 values).  ``nodes_hist[k]``: sampled tokens that
    reach exactly ``k`` nodes."""

    pairs_max: float
    pairs_mean: float
    ib_bytes_max: float
    ib_bytes_mean: float
    nodes_hist: tuple[int, ...]


@dataclass(frozen=True)
class Routing:
    node_tokens: np.ndarray       # [ranks, nodes] f8
    pairs: np.ndarray             # [ranks] f8
    stats: RouteStats


def dispatch_bytes(cfg: ModelConfig) -> float:
    """Bytes of one token in an FP8 dispatch: a byte a value plus one
    fp32 scale per 128 values."""
    return cfg.d_model + 4.0 * cfg.d_model / 128


def select_experts(z: np.ndarray, n_groups: int, groups_per_token: int,
                   k: int) -> np.ndarray:
    """``[T, k]`` experts each token routes to, from its ``[T, E]``
    routing logits: the ``groups_per_token`` groups of largest
    top-two-affinity sum, then the ``k`` largest logits inside them
    (the sigmoid is monotone, so logits order experts as affinities
    do).  Partitions, no sort: the order within the ``k`` is not kept."""
    t, e = z.shape
    per = e // n_groups
    zg = z.reshape(t, n_groups, per)
    top2 = np.partition(zg, per - 2, axis=2)[:, :, per - 2:]
    score = (1.0 / (1.0 + np.exp(-top2))).sum(axis=2)
    keep = np.argpartition(score, n_groups - groups_per_token,
                           axis=1)[:, n_groups - groups_per_token:]
    cand = np.take_along_axis(zg, keep[:, :, None], axis=1).reshape(t, -1)
    pick = np.argpartition(cand, cand.shape[1] - k, axis=1)[:, -k:]
    group = np.take_along_axis(keep, pick // per, axis=1)
    return group * per + pick % per


def route(cfg: ModelConfig, ranks: int, gpus_per_node: int, tokens: float,
          rng: np.random.Generator, *, bias_sigma: float,
          sample: int) -> Routing:
    """One layer's routing for ``ranks`` EP ranks of ``tokens`` tokens
    each, ``gpus_per_node`` ranks to a node (see the module docstring)."""
    n_nodes = ranks // gpus_per_node
    e, k = cfg.n_experts, cfg.experts_per_token
    if cfg.router_scoring != "sigmoid":
        raise ValueError(f"{cfg.name}: only sigmoid routing is drawn, "
                         f"not {cfg.router_scoring}")
    if (n_nodes * gpus_per_node != ranks or e % ranks
            or cfg.n_expert_groups % n_nodes):
        raise ValueError(f"{cfg.name}: {e} experts in {cfg.n_expert_groups} "
                         f"groups do not split over {ranks} ranks, "
                         f"{gpus_per_node} to a node")
    bias = rng.normal(0.0, bias_sigma, e).astype(np.float32)
    z = rng.standard_normal((ranks * sample, e), dtype=np.float32) + bias
    experts = select_experts(z, cfg.n_expert_groups, cfg.groups_per_token,
                             k)

    scale = tokens / sample
    hit = np.zeros((ranks * sample, n_nodes), dtype=bool)
    hit[np.arange(ranks * sample)[:, None], experts // (e // n_nodes)] = True
    node_tokens = hit.reshape(ranks, sample, n_nodes).sum(axis=1) * scale
    pairs = np.bincount((experts // (e // ranks)).ravel(),
                        minlength=ranks) * scale

    node_of = np.arange(ranks) // gpus_per_node
    remote = node_tokens.copy()
    remote[np.arange(ranks), node_of] = 0.0
    sent = remote.sum(axis=1)
    # Rank (m, i) receives from every (n, i), n != m, what they send to m.
    by_rail = remote.reshape(n_nodes, gpus_per_node, n_nodes)
    received = by_rail.sum(axis=0).T.ravel()
    ib = (sent + received) * dispatch_bytes(cfg)
    reached = hit.sum(axis=1)
    stats = RouteStats(
        pairs_max=float(pairs.max()), pairs_mean=float(pairs.mean()),
        ib_bytes_max=float(ib.max()), ib_bytes_mean=float(ib.mean()),
        nodes_hist=tuple(int(c) for c in np.bincount(
            reached, minlength=cfg.groups_per_token + 1)))
    return Routing(node_tokens=node_tokens, pairs=pairs, stats=stats)
