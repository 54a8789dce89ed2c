"""glm_gram: the IRLS Gram of a block of the code-form design as ONE Pallas
kernel, where the layout has one-hot blocks and the mesh is a TPU's.

The XLA form (``glm._gram_parts``) expands a block of rows to its one-hot
columns, writes them and their three weighted bfloat16 pieces to HBM and
multiplies ``[one-hot, B]ᵀ x [B, one-hot + others]``: the airlines frame's
628 columns make that four ``bf16[B, 622]`` operands a block and a
``622 x 628`` product whose one-hot x one-hot part is mostly zeros (a
categorical's own block is diagonal, the whole is symmetric).

Here a block's codes and the pieces of ``[wi | wi o X_other]`` stream
through VMEM, a row tile at a time, and every one-hot tile is built there
from the codes by a compare against an iota.  Each categorical is a row
GROUP (its transposed one-hot, ``[levels, T]``), which meets

* the pieces of ``[wi | wi o X_other]``: the group's weighted counts (the
  diagonal of its own block) and its block against the other columns;
* the one-hot columns of every LATER group, times each piece of ``wi``: the
  cross blocks, each pair once.

The groups run widest first, so a narrow categorical is the later side of
its pairs, a few lanes of another group's columns, and never rows in front
of wide ones.  Where the three pieces of a group's later columns fit the
lanes one piece takes, they sit side by side in ONE product.  The products
are exact as the XLA form's are (a 0/1 bfloat16 one-hot times a bfloat16
piece, summed in float32); ``top_of_parts`` puts the sums, over blocks and
shards, back into the XLA form's ``top``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tree.hist import _named_kernel
from .tree.shared import _on_tpu

_LANES = 128
_ROWS = 16                      # a bfloat16 tile's rows
_NO_CODE = 1 << 30              # a code that lights no lane
# VMEM the kernel plans for: a row tile's one-hot operands and compares,
# and the accumulators (resident across the grid, two buffers each).  A
# layout whose accumulators pass their budget keeps the XLA form.
_TILE_BYTES = 8 << 20
_ACC_BYTES = 24 << 20


@dataclasses.dataclass(frozen=True)
class _Group:
    cat: int                    # the categorical's column of the codes
    levels: int                 # its one-hot width
    later: Tuple[Tuple[int, int, int], ...]   # (cat, levels, first lane)
    span: int                   # lanes of one piece of the later columns
    packed: bool                # the three pieces side by side in one product
    lanes: int                  # lanes of the cross accumulator

    @property
    def rows(self) -> int:
        return _round(self.levels, _ROWS)


@dataclasses.dataclass(frozen=True)
class _Plan:
    groups: Tuple[_Group, ...]
    n_cat: int
    n_other: int                # the layout's other columns: numerics, intercept
    side_rows: int              # rows of the pieces of [wi | wi o X_other]
    tile: int                   # rows of a grid step

    @property
    def outputs(self) -> Tuple[Tuple[int, int], ...]:
        """The accumulators' shapes: a group's against the pieces, then
        its cross block if it has later groups."""
        return tuple(s for g in self.groups
                     for s in ((g.rows, self.side_rows), (g.rows, g.lanes))
                     if s[1])


def _acc_bytes(plan: "_Plan") -> int:
    return sum(a * b for a, b in plan.outputs) * 4 * 2


def _round(n: int, to: int) -> int:
    return -(-n // to) * to


@functools.lru_cache(maxsize=None)
def _plan(layout: tuple) -> Optional[_Plan]:
    """The kernel's static plan for ``layout``, or None where the layout
    has no one-hot block or its accumulators pass the VMEM budget."""
    widths = [w for kind, w in layout if kind == "cat"]
    if not widths:
        return None
    order = sorted(range(len(widths)), key=lambda i: -widths[i])
    groups = []
    for at, i in enumerate(order):
        later, lane = [], 0
        for j in order[at + 1:]:
            later.append((j, widths[j], lane))
            lane += widths[j]
        packed = lane > 0 and _round(3 * lane, _LANES) <= _round(lane, _LANES)
        lanes = _round(3 * lane if packed else lane, _LANES) if lane else 0
        groups.append(_Group(i, widths[i], tuple(later), lane, packed, lanes))
    n_other = sum(w for kind, w in layout if kind != "cat")
    # a row's bytes in the widest group: the transposed one-hot (int32
    # compare, bfloat16 operand) and the cross operand (mask, float32
    # select, bfloat16)
    row = max(6 * g.rows + 10 * g.lanes for g in groups)
    tile = 2048
    while tile > _LANES and tile * row > _TILE_BYTES:
        tile //= 2
    plan = _Plan(tuple(groups), len(widths), n_other,
                 _round(3 * (1 + n_other), _ROWS), tile)
    return plan if _acc_bytes(plan) <= _ACC_BYTES else None


def engages(layout: tuple) -> bool:
    """Whether a pass of the blocked IRLSM forms its Gram by the kernel:
    the layout has a one-hot block that fits the kernel's plan and is at
    least a row tile of the MXU wide (``_LANES`` levels), on a TPU.
    Everywhere else ``glm._gram_parts`` keeps the XLA product.

    The width is the rule's reading of the layout.  Where every group is
    narrower, as RuleFit's many groups of 2^depth rules are, a group's
    one-hot tile fills a few of the MXU's rows and its cross columns are
    built by one compare per later group: 50 groups of 8 make 1,225
    compares of a 512-lane row for every row of the block, and Mosaic's
    register allocator spills 158 MB of them into VMEM, so the kernel
    does not compile there (one v5e, PERF.md section 6), where XLA's
    product of the expanded block is one pass of the MXU over its 400
    one-hot columns."""
    return (_plan(layout) is not None and _on_tpu()
            and max(w for kind, w in layout if kind == "cat") >= _LANES)


def _kernel(plan: _Plan, codes_ref, side_ref, *out_refs):
    @pl.when(pl.program_id(0) == 0)
    def _():
        for out in out_refs:
            out[...] = jnp.zeros_like(out)

    codes = codes_ref[...]                              # [k, T] int32
    side = side_ref[...]                                # [side_rows, T] f32
    pieces = side.astype(jnp.bfloat16)
    w = [side[k * (1 + plan.n_other)][:, None] for k in range(3)]
    outs = iter(out_refs)
    nt = (((1,), (1,)), ((), ()))
    for g in plan.groups:
        level = jax.lax.broadcasted_iota(jnp.int32, (g.rows, 1), 0)
        hot_t = (codes[g.cat][None, :] == level).astype(jnp.bfloat16)
        out = next(outs)
        out[...] += jax.lax.dot_general(hot_t, pieces, nt,
                                        preferred_element_type=jnp.float32)
        if not g.later:
            continue
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, g.lanes), 1)
        # each later code as the lane it lights, a code of -1 as none
        at = [jnp.where(codes[j] < 0, _NO_CODE, codes[j] + first)[:, None]
              for j, _, first in g.later]

        def lit(shift):
            hit = at[0] + shift == lane
            for a in at[1:]:
                hit = hit | (a + shift == lane)
            return hit

        out = next(outs)
        if g.packed:
            cols = jnp.where(lit(2 * g.span), w[2], 0.0)
            cols = jnp.where(lit(g.span), w[1], cols)
            cols = jnp.where(lit(0), w[0], cols)
            out[...] += jnp.dot(hot_t, cols.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32)
        else:
            hit = lit(0)
            for wk in w:
                out[...] += jnp.dot(
                    hot_t, jnp.where(hit, wk, 0.0).astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)


@functools.lru_cache(maxsize=None)
def _make_call(layout: tuple, rows: int, vma: frozenset, interpret: bool):
    plan = _plan(layout)
    tile = min(plan.tile, _round(rows, _LANES))
    return _named_kernel(
        "glm_gram", kernel=functools.partial(_kernel, plan),
        grid=(_round(rows, tile) // tile,),
        in_specs=[pl.BlockSpec((plan.n_cat, tile), lambda t: (0, t),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((plan.side_rows, tile), lambda t: (0, t),
                               memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec(s, lambda t: (0, 0), memory_space=pltpu.VMEM)
                   for s in plan.outputs],
        out_shape=[jax.ShapeDtypeStruct(s, jnp.float32, vma=vma)
                   for s in plan.outputs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_acc_bytes(plan) + 2 * _TILE_BYTES + (16 << 20)),
        interpret=pltpu.InterpretParams() if interpret else False), tile


def gram_parts(layout: tuple, codes: jax.Array, pieces) -> tuple:
    """The kernel's sums over a block: ``codes`` [B, categoricals] int32,
    ``pieces`` the three bfloat16 pieces of ``[wi | wi o X_other]``
    [B, 1 + others] (``glm._bf16_pieces``).  ``top_of_parts`` reads them
    once they are summed over blocks and shards."""
    plan = _plan(layout)
    rows = codes.shape[0]
    side = jnp.concatenate([p.astype(jnp.float32) for p in pieces], axis=1).T
    call, tile = _make_call(layout, rows, frozenset(jax.typeof(codes).vma),
                            not _on_tpu())
    pad = _round(rows, tile) - rows
    codes_t = jnp.pad(codes.T, ((0, 0), (0, pad)), constant_values=-1)
    side = jnp.pad(side, ((0, plan.side_rows - side.shape[0]), (0, pad)))
    return tuple(call(codes_t, side))


def top_of_parts(layout: tuple, parts: tuple) -> jax.Array:
    """The XLA form's ``top`` ([one-hot columns, one-hot columns then the
    others], the categoricals in the layout's order) from ``gram_parts``'
    sums: a group's weighted counts on its block's diagonal, each cross
    block and its mirror, each group against the other columns."""
    plan = _plan(layout)
    r1 = 1 + plan.n_other
    blocks = {}
    other = {}
    parts = iter(parts)
    for g in plan.groups:
        wy = next(parts)[:g.levels]
        wy = wy[:, :r1] + wy[:, r1:2 * r1] + wy[:, 2 * r1:3 * r1]
        blocks[g.cat, g.cat] = jnp.diag(wy[:, 0])
        other[g.cat] = wy[:, 1:]
        if not g.later:
            continue
        cross = next(parts)[:g.levels]
        if g.packed:
            s = g.span
            cross = cross[:, :s] + cross[:, s:2 * s] + cross[:, 2 * s:3 * s]
        for j, levels, first in g.later:
            blocks[g.cat, j] = cross[:, first:first + levels]
            blocks[j, g.cat] = blocks[g.cat, j].T
    cats = range(plan.n_cat)
    return jnp.concatenate(
        [jnp.concatenate([blocks[i, j] for j in cats] + [other[i]], axis=1)
         for i in cats], axis=0)
