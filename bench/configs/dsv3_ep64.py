"""DeepSeek-V3's EP64 training stage: routed, node-limited all-to-alls.

One lane is one expert-parallel group's share of one pipeline stage of
DeepSeek-V3's training layout (arXiv:2412.19437 §3.1-3.3): 64 GPUs, 8
to a node, each a port of a big switch at its 50 GB/s InfiniBand NIC,
running ``traffic["moe_layers"]`` MoE layers forward and then backward
for one microbatch of ``traffic["tokens_per_rank"]`` tokens a rank.

Each layer is routed once from the lane's seed, as DeepSeek-V3 routes:
sigmoid affinities of N(0, 1) logits plus a per-layer expert bias, the
8 expert groups being the 8 nodes, a token keeping the 4 groups whose
two best affinities sum highest and then its 8 best experts in them.
A token crosses InfiniBand once per node it reaches, to the GPU of the
same in-node index, so each all-to-all is 448 one-flow metaflows (64
ranks x 7 other nodes) sized by the routing.  Per exchange: the
senders' tasks, the legs to each node (``D``), the node's experts
(``E``, as long as its busiest GPU), the legs back (``C``), and the
receivers' tasks, which send the next exchange.  Forward tasks ``A``
are MLA, the shared expert and the router (BF16); backward is twice the
forward, and both of its exchanges are BF16.  Sizes are MB, loads
the time in units of the 20 us one MB takes on a NIC.

A copy of ``repro.appdag.routing`` and ``repro.appdag.plans.ep_stage_dag``
as they stood when the cell was added, so the yardstick does not move
with the program; ``tests/test_ep_stage.py`` holds the two to the same
lanes and the cell's file pins them.
"""

from __future__ import annotations

import numpy as np

from repro.core.metaflow import JobDAG

MB = 1e6


def _select(z: np.ndarray, n_groups: int, groups_per_token: int,
            k: int) -> np.ndarray:
    """``[T, k]`` experts of each token from its ``[T, E]`` logits."""
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


def _route(config: dict, tokens: int, rng: np.random.Generator):
    """``(node_tokens[r, n], pairs[r])`` of one layer: rank ``r``'s
    tokens that reach node ``n`` (once per node), and the (token,
    expert) pairs rank ``r``'s experts receive."""
    ranks, sample = config["n_ports"], config["sample"]
    e, nodes = config["n_routed_experts"], ranks // config["gpus_per_node"]
    bias = rng.normal(0.0, config["bias_sigma"], e).astype(np.float32)
    z = rng.standard_normal((ranks * sample, e), dtype=np.float32) + bias
    experts = _select(z, config["n_group"], config["topk_group"],
                      config["num_experts_per_tok"])
    scale = tokens / sample
    hit = np.zeros((ranks * sample, nodes), dtype=bool)
    hit[np.arange(ranks * sample)[:, None], experts // (e // nodes)] = True
    node_tokens = hit.reshape(ranks, sample, nodes).sum(axis=1) * scale
    pairs = np.bincount((experts // (e // ranks)).ravel(),
                        minlength=ranks) * scale
    return node_tokens, pairs


def _flops(config: dict, seq_len: int) -> tuple[float, float]:
    """Forward FLOPs of one token on its own rank (MLA over a causal
    ``seq_len`` sequence, the shared experts, the router) and of one
    (token, expert) pair."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v, q_lora = config["v_head_dim"], config["q_lora_rank"]
    kv_lora = config["kv_lora_rank"]
    qk = nope + rope
    proj = (d * q_lora + q_lora * h * qk + d * (kv_lora + rope)
            + kv_lora * h * (nope + v) + h * v * d)
    keys = (seq_len + 1) / 2
    mla = 2.0 * (proj + h * (qk + v) * keys)
    expert = 2.0 * 3 * d * config["moe_intermediate_size"]
    dense = (mla + config["n_shared_experts"] * expert
             + 2.0 * d * config["n_routed_experts"])
    return dense, expert


def stage(config: dict, traffic: dict, rng: np.random.Generator) -> JobDAG:
    """One lane's job: the EP group's stage, forward then backward."""
    if traffic["microbatches"] != 1:
        raise ValueError("one microbatch a lane")
    ep, gpn = config["n_ports"], config["gpus_per_node"]
    n_nodes, layers = ep // gpn, traffic["moe_layers"]
    tokens = traffic["tokens_per_rank"]
    d = config["hidden_size"]
    d_bytes, c_bytes = d + 4.0 * d / 128, 2.0 * d
    unit_s = MB / config["nic_bytes_per_s"]
    eff = config["efficiency"]
    dense, expert = _flops(config, tokens)
    a_load = tokens * dense / (config["bf16_flops"] * eff) / unit_s
    pair_load = expert / (config["fp8_flops"] * eff) / unit_s
    routes = [_route(config, tokens, rng) for _ in range(layers)]
    node_ranks = [range(n * gpn, (n + 1) * gpn) for n in range(n_nodes)]

    job = JobDAG(name=f"deepseek-v3-ep{ep}-stage", arrival=0.0)
    senders = [f"f0/A{r}" for r in range(ep)]
    for r in range(ep):
        job.add_task(senders[r], load=a_load, machine=r)
    order = ([("f", k) for k in range(layers)]
             + [("b", k) for k in reversed(range(layers))])
    for phase, k in order:
        p, (node_tokens, pairs) = f"{phase}{k}", routes[k]
        grow, d_size = (1.0, d_bytes) if phase == "f" else (2.0, c_bytes)
        legs = [(r, n, (r, n * gpn + r % gpn, float(node_tokens[r][n])))
                for r in range(ep) for n in range(n_nodes)
                if n != r // gpn and node_tokens[r][n] > 0]
        into: list[list[str]] = [[] for _ in range(n_nodes)]
        for r, n, (src, dst, tok) in legs:
            into[n].append(f"{p}/D{r}>{n}")
            job.add_metaflow(into[n][-1], [(src, dst, tok * d_size / MB)],
                             deps=[senders[r]])
        for n in range(n_nodes):
            busiest = max(pairs[r] for r in node_ranks[n])
            job.add_task(f"{p}/E{n}", load=grow * busiest * pair_load,
                         machine=n * gpn,
                         deps=into[n] + [senders[r] for r in node_ranks[n]])
        back: list[list[str]] = [[] for _ in range(ep)]
        for r, n, (src, dst, tok) in legs:
            back[r].append(f"{p}/C{n}>{r}")
            job.add_metaflow(back[r][-1], [(dst, src, tok * c_bytes / MB)],
                             deps=[f"{p}/E{n}"])
        if phase == "b":
            receivers, load = [f"b{k}/A{r}" for r in range(ep)], 2 * a_load
        elif k + 1 < layers:
            receivers, load = [f"f{k + 1}/A{r}" for r in range(ep)], a_load
        else:
            receivers, load = [f"turn{r}" for r in range(ep)], 0.0
        for r in range(ep):
            job.add_task(receivers[r], load=load, machine=r,
                         deps=[f"{p}/E{r // gpn}"] + back[r])
        senders = receivers
    job.validate()
    return job


def build_lanes(seeds, traffic: dict, config: dict) -> list:
    """``(n_ports, [job])`` of each seed: the stage routed from it."""
    return [(config["n_ports"],
             [stage(config, traffic, np.random.default_rng(seed))])
            for seed in seeds]
