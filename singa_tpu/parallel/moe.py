"""Mixture-of-Experts FFN with expert parallelism (GShard-style).

No reference equivalent (the reference is data-parallel only, SURVEY.md
§2.4); this is the TPU-native 'ep' axis: expert weights shard over the
mesh 'expert' axis (one expert group per peer), tokens shard over the
batch-like axes, and two tiled ``lax.all_to_all`` exchanges carry each
token to its expert's peer and back — the canonical MoE layout where the
dispatch rides the ICI.

Capacity-factor token dropping, top-1/top-2 gating with normalized
combine weights, and the load-balance auxiliary loss follow the GShard
formulation (einsum dispatch/combine over static shapes, so the whole
layer jits into one XLA computation). Outside an active mesh context the
all-to-alls degrade to identity and the same code computes the dense
(single-device) MoE.
"""

from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from ..autograd_base import Operator
from ..layer import Layer, _param
from ..tensor import Tensor
from .communicator import active_axis, axis_size
from .gspmd import expert_spec


class _MoEFFN(Operator):
    """(T, D) tokens -> (T, D) expert-mixed output + scalar aux loss."""

    def __init__(self, n_experts, top_k, capacity_factor, axis_name,
                 batch_axes):
        super().__init__()
        self.E = n_experts
        self.k = top_k
        self.cf = capacity_factor
        self.axis_name = axis_name
        self.batch_axes = batch_axes

    def forward(self, x, wg, w1, b1, w2, b2):
        T, D = x.shape
        E, k = self.E, self.k
        C = max(1, math.ceil(k * T * self.cf / E))
        f32 = jnp.float32
        gates = jax.nn.softmax(jnp.dot(x.astype(f32), wg.astype(f32)))

        # iterative top-k: pick, reserve capacity, mask out, repeat;
        # dispatch and (unnormalized) combine accumulate per round from
        # the same keep/slot increment
        masked = gates
        count = jnp.zeros((E,), f32)          # tokens already queued
        dispatch = jnp.zeros((T, E, C), f32)
        combine = jnp.zeros((T, E, C), f32)
        picked_gates = []
        first_mask = None
        for _ in range(k):
            idx = jnp.argmax(masked, axis=1)              # (T,)
            hot = jax.nn.one_hot(idx, E, dtype=f32)       # (T, E)
            if first_mask is None:
                first_mask = hot
            pos = jnp.cumsum(hot, axis=0) - hot + count   # queue position
            keep = (pos < C).astype(f32) * hot
            count = count + keep.sum(axis=0)
            chot = jax.nn.one_hot(
                (pos * hot).sum(axis=1).astype(jnp.int32), C,
                dtype=f32)                                # (T, C)
            inc = keep[:, :, None] * chot[:, None, :]     # (T, E, C)
            dispatch = dispatch + inc
            g = (gates * hot).sum(axis=1)                 # (T,)
            combine = combine + g[:, None, None] * inc
            picked_gates.append(g)
            masked = masked * (1.0 - hot)

        # combine weights: raw gate for top-1 (Switch — the gate gradient
        # flows through the output scale), normalized across picks for
        # top-k>=2 (GShard)
        if k > 1:
            denom = sum(picked_gates) + 1e-9              # (T,)
            combine = combine / denom[:, None, None]

        # dispatch -> expert-major buffer, exchange over the expert axis
        ein = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
        if active_axis(self.axis_name):
            ep = axis_size(self.axis_name)
            if E % ep != 0:
                raise ValueError(
                    f"n_experts={E} must divide by the '{self.axis_name}' "
                    f"mesh degree {ep}")
            ein = lax.all_to_all(ein, self.axis_name, 0, 1, tiled=True)
        # expert FFN on the local expert group (g = local experts)
        h = jnp.einsum("gcd,gdf->gcf", ein, w1) + b1[:, None, :]
        h = jax.nn.gelu(h)
        out_e = jnp.einsum("gcf,gfd->gcd", h, w2) + b2[:, None, :]
        if active_axis(self.axis_name):
            out_e = lax.all_to_all(out_e, self.axis_name, 1, 0, tiled=True)
        y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), out_e)

        # load-balance aux (GShard): E * sum_e mean_t(gate_e)*mean_t(pick1_e)
        # — the means must be GLOBAL over the token batch: under sharding,
        # a mean of per-shard products is not the product of global means
        gmean = gates.mean(axis=0)
        mmean = first_mask.mean(axis=0)
        for ax in self.batch_axes:
            if active_axis(ax):
                gmean = lax.pmean(gmean, ax)
                mmean = lax.pmean(mmean, ax)
        aux = E * jnp.sum(gmean * mmean)
        return y, aux.astype(x.dtype)


class MoEFFN(Layer):
    """Drop-in FFN block whose experts shard over the mesh 'expert' axis.

    ``forward`` returns the mixed output; the load-balance auxiliary loss
    of the call is exposed as ``self.aux_loss`` — a tape Tensor that is
    only valid INSIDE the same ``train_one_batch`` (add
    ``alpha * aux_loss`` to the loss there; under graph mode it is a
    traced value that dies with the trace, so it cannot be read for
    logging after a compiled step).

    ``n_experts`` must divide by the expert-axis degree; with no active
    mesh the same layer computes the dense MoE on one device.
    """

    def __init__(self, n_experts, d_ff, top_k=2, capacity_factor=1.25,
                 axis_name="expert", batch_axes=("data", "expert", "seq")):
        super().__init__()
        if top_k > n_experts:
            raise ValueError(
                f"top_k={top_k} cannot exceed n_experts={n_experts}")
        self.n_experts = n_experts
        self.d_ff = d_ff
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.axis_name = axis_name
        self.batch_axes = batch_axes
        self.aux_loss = None

    def initialize(self, x):
        D, F, E = x.shape[-1], self.d_ff, self.n_experts
        dev = x.device
        # router stays f32 (softmax gating needs the range; x bf16 @ wg
        # f32 promotes to f32 so routing is full-precision either way);
        # experts follow the input dtype like every other matmul layer
        self.wg = _param((D, E), dev)
        self.wg.gaussian(0.0, math.sqrt(1.0 / D))
        self.w1 = _param((E, D, F), dev, dtype=x.dtype)
        self.w1.gaussian(0.0, math.sqrt(2.0 / (D + F)))
        self.b1 = _param((E, F), dev, dtype=x.dtype)
        self.w2 = _param((E, F, D), dev, dtype=x.dtype)
        self.w2.gaussian(0.0, math.sqrt(2.0 / (D + F)))
        self.b2 = _param((E, D), dev, dtype=x.dtype)
        if self.axis_name:
            # expert banks announce their layout through the shared
            # gspmd vocabulary, like every other sharded layer
            for t in (self.w1, self.b1, self.w2, self.b2):
                t.spec = expert_spec(self.axis_name)

    def forward(self, x):
        from .. import autograd
        shape = x.shape
        if len(shape) > 2:
            x = autograd.reshape(x, (-1, shape[-1]))
        y, aux = _MoEFFN(self.n_experts, self.top_k, self.capacity_factor,
                         self.axis_name, self.batch_axes)(
            x, self.wg, self.w1, self.b1, self.w2, self.b2)
        self.aux_loss = aux
        if len(shape) > 2:
            y = autograd.reshape(y, shape)
        return y

    def _own_params(self):
        return {"wg": self.wg, "w1": self.w1, "b1": self.b1,
                "w2": self.w2, "b2": self.b2}


# ---------------------------------------------------------------------------
# the share-aware expert layer: told which experts it holds
# ---------------------------------------------------------------------------

class Route(collections.namedtuple("Route", "score renormalise scale")):
    """A top-k routing rule as data: ``score`` is the function that turns
    the router's logits into scores (``"sigmoid"`` | ``"softmax"``, over
    all of the router's columns), ``renormalise`` whether the picks'
    weights are divided by their sum, ``scale`` a factor on the weights
    (applied once, after the renormalisation if there is one)."""

    __slots__ = ()

    def __new__(cls, score="sigmoid", renormalise=True, scale=1.0):
        if score not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown score function {score!r}")
        return super().__new__(cls, score, bool(renormalise), float(scale))


SIGMOID_TOPK = Route()


def route_topk(h, router, top_k, route=SIGMOID_TOPK, bias=None):
    """Scores over ALL of the router's columns in float32, the ``top_k``
    best, their scores as weights by the rule ``route``: ``(idx (T, k)
    int32, w (T, k) float32)``. ``bias`` (columns,) is added to the
    scores for the CHOICE only: the weights are the picks' own scores.
    The matrix product runs at the highest precision (a TPU's default
    rounds float32 operands to bf16): the router is hundreds of columns
    wide, and which expert comes last hangs on the fourth decimal."""
    z = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                precision=lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(z) if route.score == "sigmoid" \
        else jax.nn.softmax(z, axis=-1)
    if bias is None:
        w, idx = lax.top_k(s, top_k)
    else:
        _, idx = lax.top_k(s + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
    if route.renormalise:
        w = w / jnp.sum(w, -1, keepdims=True)
    if route.scale != 1.0:
        w = w * route.scale
    return idx.astype(jnp.int32), w


def route_sigmoid_topk(h, router, top_k):
    """:func:`route_topk` by its default rule: sigmoid scores, the
    picks' weights renormalised over the picks, no bias, no factor."""
    return route_topk(h, router, top_k)


def _gated(x, w_gate, w_up, w_down):
    """One SwiGLU expert on rows ``x``: operands in ``x.dtype``, sums
    and the activation in float32."""
    f32 = jnp.float32
    g = jnp.dot(x, w_gate, preferred_element_type=f32)
    u = jnp.dot(x, w_up, preferred_element_type=f32)
    return jnp.dot((jax.nn.silu(g) * u).astype(x.dtype), w_down,
                   preferred_element_type=f32)


def rows_in_blocks(fn, x, n_blocks, block_rows):
    """``fn`` on the first ``n_blocks`` blocks of ``block_rows`` rows of
    ``x`` (T, D), the rest left nought: ``n_blocks`` is read on the
    device (a loop of that length), so a padded prompt pays for the
    blocks that hold a token and not for its padding. ``fn`` maps
    (block_rows, D) to (block_rows, D_out)."""
    out = jax.eval_shape(fn, jax.ShapeDtypeStruct(
        (block_rows, x.shape[1]), x.dtype))

    def one(i, acc):
        rows = lax.dynamic_slice_in_dim(x, i * block_rows, block_rows)
        return lax.dynamic_update_slice_in_dim(
            acc, fn(rows).astype(out.dtype), i * block_rows, 0)

    return lax.fori_loop(0, n_blocks, one,
                         jnp.zeros((x.shape[0], out.shape[1]), out.dtype))


def _routed_dense(h, local, w, here, w_gate, w_up, w_down):
    """Every held expert on every row, weighted by its pick's weight or
    nought: right where the rows are few (a decode tick), since each
    expert's matrices are read once whatever the rows."""
    f32 = jnp.float32
    G = w_gate.shape[0]
    g = jnp.einsum("td,gdf->gtf", h, w_gate, preferred_element_type=f32)
    u = jnp.einsum("td,gdf->gtf", h, w_up, preferred_element_type=f32)
    y = jnp.einsum("gtf,gfd->gtd", (jax.nn.silu(g) * u).astype(h.dtype),
                   w_down, preferred_element_type=f32)
    hot = (local[:, :, None] == jnp.arange(G)[None, None, :]) \
        & here[:, :, None]                                   # (T, k, G)
    weight = jnp.sum(jnp.where(hot, w[:, :, None], 0.0), axis=1)  # (T, G)
    return jnp.einsum("tg,gtd->td", weight, y)


def _routed_sorted(h, group, counts, w, w_gate, w_up, w_down, tile):
    """The pairs routed here, sorted by expert and worked off in tiles of
    ``tile`` rows, each tile one expert's: as many tiles as the pairs
    need (a loop whose length is read on the device), so the work
    follows the pairs and no pair is dropped, whatever the router does.
    ``group``: (T*k,) held expert of each pair, G for a pair routed
    elsewhere; ``counts``: (G,) pairs an expert. Static shapes
    throughout."""
    T, k = w.shape
    i32 = jnp.int32
    order = jnp.argsort(group, stable=True).astype(i32)      # absent last
    tiles = (counts + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)
    tile_start = tile_end - tiles
    row_start = jnp.cumsum(counts) - counts
    flat_w = w.reshape(-1)
    lane = jnp.arange(tile, dtype=i32)

    def one_tile(i, acc):
        g = jnp.searchsorted(tile_end, i, side="right").astype(i32)
        rows = row_start[g] + (i - tile_start[g]) * tile + lane
        live = rows < row_start[g] + counts[g]
        pair = order[jnp.minimum(rows, T * k - 1)]
        tok = pair // k
        y = _gated(h[tok], w_gate[g], w_up[g], w_down[g])
        y = y * jnp.where(live, flat_w[pair], 0.0)[:, None]
        # a token picks an expert once, so live rows of a tile never
        # share a token; dead rows add nought
        return acc.at[tok].add(y)

    return lax.fori_loop(0, tile_end[-1], one_tile,
                         jnp.zeros(h.shape, jnp.float32))


# up to DENSE_ROWS rows every held expert runs on every row (a decode
# tick: each expert's matrices are read once whatever the rows); above,
# the pairs are sorted by expert and worked off in tiles of SORTED_TILE
DENSE_ROWS = 128
SORTED_TILE = 256


# ---------------------------------------------------------------------------
# the grouped product: rows sorted by expert times each expert's matrix,
# JAX's megablox kernels (gmm, tgmm), whose grid visits the row tiles of
# the groups held here and no others (its length is read on the device)
# ---------------------------------------------------------------------------

# rows a grid step of the grouped product: a group's rows are worked off in
# tiles of GROUPED_TILE, a tile that two groups share is visited by each
GROUPED_TILE = 128
# largest matrix block a grid step holds, bytes (twice, double-buffered,
# within the v5e's 16 MiB of scoped VMEM beside the row tiles)
_BLOCK_BYTES = 4 * 2 ** 20


def _tiling(m, k, n):
    """megablox's tile sizes for an (m, k) x (k, n) product: GROUPED_TILE
    rows, all of k, and all n columns where a (k, n) bf16 block fits
    _BLOCK_BYTES, else the largest multiple of 128 lanes that divides n
    and fits."""
    if k * n * 2 <= _BLOCK_BYTES or n % 128:
        return GROUPED_TILE, k, n
    fit = max(128, _BLOCK_BYTES // (k * 2) // 128 * 128)
    return GROUPED_TILE, k, next(t for t in range(fit, 0, -128)
                                 if n % t == 0)


def _interpret():
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(lhs, rhs, sizes, out_dtype=jnp.float32):
    """The grouped product: ``lhs`` (M, K) rows sorted by group, M a
    multiple of GROUPED_TILE, ``rhs`` (G, K, N) one matrix a group,
    ``sizes`` (G + 1,) int32 rows a group, the last group's rows (pairs
    routed to experts held elsewhere) left nought. (M, N) in
    ``out_dtype``; operands in their own dtype, sums in float32. The grid
    visits the row tiles the first G groups hold and no others, in the
    forward (megablox ``gmm``) and in both halves of the reverse: the
    rows' gradient (``gmm`` against each matrix turned over) and the
    matrices' (``tgmm``, each group's rows turned over against its rows of
    the cotangent), the cotangent taken in the operands' dtype."""
    return gmm(lhs, rhs, sizes, out_dtype, _tiling,
               interpret=_interpret())


def _grouped_fwd(lhs, rhs, sizes, out_dtype):
    return grouped_matmul(lhs, rhs, sizes, out_dtype), (lhs, rhs, sizes)


def _grouped_bwd(out_dtype, res, g):
    lhs, rhs, sizes = res
    g = g.astype(lhs.dtype)
    dlhs = gmm(g, rhs, sizes, lhs.dtype, _tiling, transpose_rhs=True,
               interpret=_interpret())
    drhs = tgmm(lhs.swapaxes(0, 1), g, sizes, rhs.dtype, _tiling,
                num_actual_groups=rhs.shape[0], interpret=_interpret())
    return dlhs, drhs, np.zeros(sizes.shape, jax.dtypes.float0)


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def _routed_grouped(h, group, counts, w, w_gate, w_up, w_down):
    """The pairs routed here through :func:`grouped_matmul`: sorted by
    held expert (pairs routed elsewhere last), gathered, each expert's
    SwiGLU on its own rows, weighted, added back to their tokens. None is
    dropped; differentiable in ``h``, ``w`` (so the router) and the three
    banks. The products' grid follows the pairs held here; their operands
    and outputs, the gather, the SwiGLU between them, the weighting and the
    scatter-add (and their reverses) are static shapes over all T * k
    pairs, the rows of pairs held elsewhere nought.
    Returns (y (T, D) float32, rows the grouped product computes)."""
    T, k = w.shape
    G = w_gate.shape[0]
    if G == 0:
        return jnp.zeros(h.shape, jnp.float32), jnp.int32(0)
    M = -(-T * k // GROUPED_TILE) * GROUPED_TILE
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    if M > T * k:
        order = jnp.concatenate(
            [order, jnp.full((M - T * k,), T * k, jnp.int32)])
    here = jnp.sum(counts)
    sizes = jnp.concatenate([counts, (M - here)[None]]).astype(jnp.int32)
    tok = jnp.minimum(order, T * k - 1) // k
    xs = h[tok]
    f32 = jnp.float32
    gate = grouped_matmul(xs, w_gate, sizes, f32)
    up = grouped_matmul(xs, w_up, sizes, f32)
    y = grouped_matmul((jax.nn.silu(gate) * up).astype(h.dtype), w_down,
                       sizes, f32)
    live = jnp.arange(M) < here
    weight = jnp.where(live, jnp.concatenate(
        [w.reshape(-1), jnp.zeros((M - T * k,), f32)])[order], 0.0)
    out = jnp.zeros((T, h.shape[1]), f32).at[tok].add(y * weight[:, None])
    # the tiles the grid visits: a group's rows rounded out to whole
    # tiles at both ends
    ends = jnp.cumsum(counts)
    starts = ends - counts
    tiles = jnp.where(counts > 0, -(-ends // GROUPED_TILE)
                      - starts // GROUPED_TILE, 0)
    return out, jnp.sum(tiles) * GROUPED_TILE


def _weights_of_a_share_alone(w, present):
    """``w`` (T, k) as it is in the forward; its gradient that of
    ``w * sum_present(w) / sum_present(w)`` with the numerator held
    constant: over the picks ``present`` (T, k) the weights renormalised
    among themselves and scaled back to their share of the whole, the
    picks not present left without a gradient. Under a rule that
    renormalises over all the picks this is the gradient the whole layer
    would give if each absent pick's output had the present picks' mean
    gradient, weighted by their weights: nothing flows to an absent
    pick's score, and the present picks' scores, weighted by themselves,
    get gradients that sum to nought."""
    held = jnp.sum(jnp.where(present, w, 0.0), -1, keepdims=True)
    r = w * lax.stop_gradient(held) / jnp.where(held > 0, held, 1.0)
    return lax.stop_gradient(w) + (r - lax.stop_gradient(r))


def expert_share_ffn(p, h, *, top_k, held_from=0, h_route=None, rows=None,
                     axis_name=None, blocks=None, route=SIGMOID_TOPK,
                     n_zero=0, grouped=False, alone=False):
    """The part of a top-k expert layer that ONE share gives.

    ``p``: ``router`` (D, E + n_zero) over all E experts and the
    ``n_zero`` experts without weights behind them; ``router_bias``
    (E + n_zero,), where present, added to the scores for the choice
    only; ``w_gate``/``w_up`` (G, D, F) and ``w_down`` (G, F, D), the G
    experts held here (experts ``held_from .. held_from + G - 1``);
    ``s_gate``/``s_up`` (S, D, F), ``s_down`` (S, F, D), the shared
    experts, held whole (absent or S = 0: none).
    ``h``: (T, D) rows in the compute dtype; ``h_route``: the same rows
    in float32 for the router (default ``h``); ``rows``: (T,) bool,
    False for padding (such rows are routed nowhere and counted
    nowhere); ``blocks``: ``(n_blocks, block_rows)`` when only the
    first ``n_blocks`` (a device scalar) blocks of rows hold a token —
    the shared experts then run on those blocks alone
    (:func:`rows_in_blocks`), as the routed ones do by their pairs;
    ``route``: the routing rule (:class:`Route`; default sigmoid
    scores renormalised over the picks); ``grouped``: above DENSE_ROWS
    rows, work the pairs off by :func:`grouped_matmul` (Pallas kernels
    with a reverse: the path to train on) rather than by the tile loop
    (a loop of a length read on the device, which has no reverse). Both
    paths below DENSE_ROWS and the grouped path above are differentiable
    in ``h``, ``h_route``, the router and every bank. ``alone``: this
    share is trained without the exchange, so the outputs of the picks
    routed elsewhere never come back and their gradient is missing; the
    picks' scores then take their gradient as if each missing output
    were the picks here on average: the weights of the picks whose
    outputs are here are renormalised over those picks, their share of
    the whole held constant. Picks routed elsewhere get no gradient, and
    the scores of the picks here none toward more or less of that
    share, so the router does not learn to prefer the experts held here.
    The forward is unchanged, bit for bit.

    Routes over all E + n_zero columns, weighs the picks by ``route``
    over all ``top_k`` of them, computes only the pairs whose expert
    lives here and drops none; picks that go to absent experts add
    nothing. A pick ``>= E`` is an identity expert: it adds its weight
    times the row itself (float32, from the rows the experts read), no
    matrix and no FLOP, on every share alike. No capacity factor. The
    shared experts' mean is added once. On an active ``axis_name``
    every peer holds its own G experts (``held_from`` is then the
    peer's index times G), all peers see the same rows, the routed
    parts are summed over the axis, and the identity experts' term is
    added once, outside that sum; on one chip the layer runs without
    the exchange.

    Returns ``(y (T, D) float32, stats)`` with ``stats`` int32 scalars:
    ``pairs_here``, ``pairs_absent``, ``pairs_zero`` (picks of identity
    experts), ``experts_touched`` (held experts that got any pair);
    the three kinds of pairs add up to real rows x ``top_k``. The
    grouped path adds ``load_max`` (most pairs a held expert got) and
    ``rows_computed`` (rows the grouped product worked: the pairs here
    and the rest of the tiles they begin and end in)."""
    T = h.shape[0]
    G = p["w_gate"].shape[0]
    E = p["router"].shape[1] - n_zero
    if axis_name is not None and active_axis(axis_name):
        held_from = lax.axis_index(axis_name) * G
    with jax.named_scope("moe_route"):
        idx, w = route_topk(h if h_route is None else h_route,
                            p["router"], top_k, route,
                            p.get("router_bias"))
        local = idx - held_from
        real = jnp.ones((T,), bool) if rows is None else rows
        here = (local >= 0) & (local < G) & (idx < E) & real[:, None]
        group = jnp.where(here, local, G).reshape(-1)
        counts = jnp.zeros((G + 1,), jnp.int32).at[group].add(1)[:G]
        pairs_here = jnp.sum(counts)
        zero = (idx >= E) & real[:, None]
        pairs_zero = jnp.sum(zero.astype(jnp.int32))
        stats = {"pairs_here": pairs_here,
                 "pairs_absent": jnp.sum(real.astype(jnp.int32)) * top_k
                 - pairs_here - pairs_zero,
                 "pairs_zero": pairs_zero,
                 "experts_touched": jnp.sum((counts > 0).astype(jnp.int32))}
        if alone:
            if axis_name is not None and active_axis(axis_name):
                raise ValueError("alone: a share on an active axis gets "
                                 "the other shares' outputs back")
            w = _weights_of_a_share_alone(w, here | zero)
    with jax.named_scope("moe_experts"):
        if T <= DENSE_ROWS:
            y = _routed_dense(h, local, w, here, p["w_gate"], p["w_up"],
                              p["w_down"])
        elif grouped:
            y, stats["rows_computed"] = _routed_grouped(
                h, group, counts, w, p["w_gate"], p["w_up"], p["w_down"])
            stats["load_max"] = jnp.max(counts, initial=0)
        else:
            y = _routed_sorted(h, group, counts, w, p["w_gate"], p["w_up"],
                               p["w_down"], min(SORTED_TILE, T))
        if axis_name is not None and active_axis(axis_name):
            y = lax.psum(y, axis_name)
    if n_zero:
        with jax.named_scope("moe_zero"):
            y = y + jnp.sum(jnp.where(zero, w, 0.0), axis=-1,
                            keepdims=True) * h.astype(jnp.float32)
    with jax.named_scope("moe_shared"):
        S = p["s_gate"].shape[0] if "s_gate" in p else 0

        def shared(rows_):
            return sum(_gated(rows_, p["s_gate"][j], p["s_up"][j],
                              p["s_down"][j]) for j in range(S)) / S

        if S:
            y = y + (shared(h) if blocks is None
                     else rows_in_blocks(shared, h, *blocks))
    return y, stats


class _ExpertShareFFN(Operator):
    """Tape node of :func:`expert_share_ffn` on its grouped path, so
    differentiable in the rows, the router and every bank."""

    def __init__(self, top_k, held_from, axis_name):
        super().__init__()
        self.kw = dict(top_k=top_k, held_from=held_from,
                       axis_name=axis_name, grouped=True)
        self.stats = None

    def forward(self, h, *leaves):
        y, self.stats = expert_share_ffn(
            dict(zip(ExpertShareFFN.LEAVES, leaves)), h, **self.kw)
        return y.astype(h.dtype)


class ExpertShareFFN(Layer):
    """Sigmoid top-k experts of which this layer holds ``held_count``
    (from ``held_from``) out of ``n_experts``, with ``n_shared`` shared
    experts averaged beside them — :func:`expert_share_ffn` as a layer,
    trainable (its grouped path). ``self.stats`` holds the last call's
    counts."""

    LEAVES = ("router", "w_gate", "w_up", "w_down", "s_gate", "s_up",
              "s_down")

    def __init__(self, n_experts, d_ff, top_k, held_count=None,
                 held_from=0, n_shared=0, axis_name="expert",
                 init_std=0.02, out_std=None):
        super().__init__()
        held_count = n_experts if held_count is None else held_count
        if top_k > n_experts or held_from + held_count > n_experts:
            raise ValueError(
                f"top_k={top_k}, held {held_from}..{held_from + held_count}"
                f" do not fit n_experts={n_experts}")
        self.n_experts, self.d_ff, self.top_k = n_experts, d_ff, top_k
        self.held_count, self.held_from = held_count, held_from
        self.n_shared, self.axis_name = n_shared, axis_name
        self.init_std = init_std
        self.out_std = init_std if out_std is None else out_std
        self.stats = None

    def initialize(self, x):
        D, F, dev = x.shape[-1], self.d_ff, x.device
        G, S = self.held_count, self.n_shared
        shapes = {"router": (D, self.n_experts),
                  "w_gate": (G, D, F), "w_up": (G, D, F),
                  "w_down": (G, F, D), "s_gate": (S, D, F),
                  "s_up": (S, D, F), "s_down": (S, F, D)}
        for name, shape in shapes.items():
            t = _param(shape, dev, dtype=x.dtype)
            std = self.out_std if name.endswith("down") else self.init_std
            if len(shape) == 3 and shape[0]:
                # a bank is drawn a matrix at a time: one draw of a
                # 0.5 GB bank holds several times that in temporaries
                t.data = jnp.stack([
                    _param(shape[1:], dev, dtype=x.dtype).gaussian(0.0, std)
                    .data for _ in range(shape[0])])
            else:
                t.gaussian(0.0, std)
            if self.axis_name and name.startswith("w_"):
                t.spec = expert_spec(self.axis_name)
            setattr(self, name, t)

    def forward(self, x):
        from .. import autograd
        shape = x.shape
        if len(shape) > 2:
            x = autograd.reshape(x, (-1, shape[-1]))
        op = _ExpertShareFFN(self.top_k, self.held_from, self.axis_name)
        y = op(x, *(getattr(self, n) for n in self.LEAVES))
        self.stats = op.stats
        return autograd.reshape(y, shape) if len(shape) > 2 else y

    def _own_params(self):
        return {n: getattr(self, n) for n in self.LEAVES}


__all__ = ["MoEFFN", "ExpertShareFFN", "expert_share_ffn", "Route",
           "route_topk", "route_sigmoid_topk", "rows_in_blocks",
           "grouped_matmul"]
