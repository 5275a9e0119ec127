"""torchgpipe.balance analogue: automatic layer -> stage partitioning.

The paper's ``torchgpipe.balance`` profiles per-layer resource use and applies
the block-partition algorithm of Bárány & Grinberg [2] to find a contiguous
partition with small pairwise discrepancy.  In a construct-and-run framework
the profiling step maps naturally onto per-layer compiled HLO cost analysis
(``balance_by_flops``) or parameter byte counts (``balance_by_size``) — no
wall-clock run is required.

``block_partition`` solves the canonical contiguous-partition minimax problem
exactly (binary search on the bottleneck value + greedy feasibility check,
O(L log sum)).  This dominates the pairwise-discrepancy heuristic of [2] for
our purpose (minimizing the slowest stage = pipeline period).
"""
from __future__ import annotations

from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp



def _feasible(costs: Sequence[float], n: int, cap: float) -> bool:
    blocks, acc = 1, 0.0
    for c in costs:
        if c > cap:
            return False
        if acc + c > cap:
            blocks += 1
            acc = c
            if blocks > n:
                return False
        else:
            acc += c
    return True


def block_partition(costs: Sequence[float], n: int) -> List[int]:
    """Partition ``costs`` into ``n`` contiguous blocks minimizing the max
    block sum.  Returns per-block sizes (len == n, sums to len(costs)).

    Every block is non-empty when ``len(costs) >= n``; otherwise trailing
    blocks are empty (the pipeline pads them with identity stages).
    """
    costs = [float(c) for c in costs]
    if n < 1:
        raise ValueError("need n >= 1")
    if len(costs) < n:
        return [1] * len(costs) + [0] * (n - len(costs))
    lo = max(costs) if costs else 0.0
    hi = sum(costs)
    for _ in range(64):
        mid = 0.5 * (lo + hi)
        if _feasible(costs, n, mid):
            hi = mid
        else:
            lo = mid
    cap = hi * (1 + 1e-12)
    # greedy split under cap, then rebalance so no block is empty
    sizes: List[int] = []
    acc, cnt = 0.0, 0
    for c in costs:
        if acc + c > cap and cnt > 0:
            sizes.append(cnt)
            acc, cnt = c, 1
        else:
            acc += c
            cnt += 1
    sizes.append(cnt)
    while len(sizes) < n:
        # split the largest block (by cost) that has >= 2 layers
        starts = [sum(sizes[:k]) for k in range(len(sizes))]
        best, best_cost = None, -1.0
        for k, sz in enumerate(sizes):
            if sz >= 2:
                c = sum(costs[starts[k]:starts[k] + sz])
                if c > best_cost:
                    best, best_cost = k, c
        if best is None:
            sizes.append(0)
            continue
        sz = sizes[best]
        sizes[best:best + 1] = [sz // 2 + sz % 2, sz // 2]
    assert len(sizes) == n and sum(sizes) == len(costs)
    return sizes


def partition_bounds(sizes: Sequence[int]) -> List[int]:
    """Cumulative stage boundaries: stage j owns layers [b[j], b[j+1])."""
    out = [0]
    for s in sizes:
        out.append(out[-1] + s)
    return out


def balance_by_size(param_bytes: Sequence[int], n: int) -> List[int]:
    """Partition layers by parameter byte counts (torchgpipe balance_by_size)."""
    return block_partition(param_bytes, n)


def balance_by_flops(layer_fns: Sequence[Callable], example_inputs, n: int) -> List[int]:
    """Partition layers by compiled per-layer HLO FLOPs.

    This is the construct-and-run analogue of torchgpipe's ``balance_by_time``
    profiling pass: instead of timing an eager forward, each layer is lowered
    and compiled standalone and its ``cost_analysis()['flops']`` is the cost.
    ``example_inputs[k]`` is the (abstract or concrete) input of layer ``k``.
    """
    costs = []
    for fn, x in zip(layer_fns, example_inputs):
        compiled = jax.jit(fn).lower(x).compile()
        costs.append(float(compiled.cost_analysis().get("flops", 0.0))
                     or 1.0)
    return block_partition(costs, n)


def arch_layer_costs(arch, seq_len: int = 0):
    """Analytic per-layer (flops_per_example, param_bytes) for an ArchConfig.

    The planner's analogue of torchgpipe's profiling pass, computed from the
    architecture instead of a wall-clock run.  Layers are listed in pipeline
    order — for encoder-decoder archs the ``enc_layers`` encoder blocks come
    first, then the ``n_layers`` decoder blocks (which carry the extra
    cross-attention term).  Only *relative* weights matter for partitioning;
    the flops model is matmul-dominant: ``2 * params * tokens`` plus the
    attention score/value quadratic term.
    """
    d = arch.d_model
    dtype_bytes = 2 if arch.param_dtype in ("bfloat16", "float16") else 4
    attn = arch.attn
    heads_dim = attn.n_heads * attn.head_dim if attn is not None and \
        attn.kind != "none" else 0

    def attn_quad(tokens: int, kv_len: int) -> float:
        # QK^T + attn @ V: 2 * 2 * tokens * kv_len * n_heads * head_dim
        return 4.0 * tokens * kv_len * heads_dim

    base_params = arch.layer_params()
    cross_params = 4 * d * heads_dim if arch.is_encdec else 0
    seq = seq_len or 1
    enc_len = arch.enc_len or seq

    flops: List[float] = []
    bytes_: List[int] = []
    if arch.is_encdec:
        for _ in range(arch.enc_layers):
            flops.append(2.0 * base_params * enc_len + attn_quad(enc_len, enc_len))
            bytes_.append(base_params * dtype_bytes)
        for _ in range(arch.n_layers):
            flops.append(2.0 * (base_params + cross_params) * seq
                         + attn_quad(seq, seq) + attn_quad(seq, enc_len))
            bytes_.append((base_params + cross_params) * dtype_bytes)
    else:
        per = 2.0 * base_params * seq + (attn_quad(seq, seq) if heads_dim else 0.0)
        for _ in range(arch.n_layers):
            flops.append(per)
            bytes_.append(base_params * dtype_bytes)
    return flops, bytes_


def max_block_cost(costs: Sequence[float], sizes: Sequence[int]) -> float:
    b = partition_bounds(sizes)
    return max((sum(costs[b[j]:b[j + 1]]) for j in range(len(sizes))), default=0.0)
