"""GSPMD-native partitioner: ONE sharding vocabulary for the whole stack.

Every PartitionSpec in this codebase — tensor/vocab-parallel training
layers, MoE expert banks, the serving engine's params and KV state
(ring AND paged), checkpoint live-sharding templates — is constructed
HERE, over a named mesh whose two serving axes are ``batch`` (data-like:
slots, request rows) and ``model`` (tensor-parallel: attention heads,
MLP hidden, vocab). The execution model is the scaling-book /
SNIPPETS.md [2] recipe: annotate inputs with
:class:`~jax.sharding.NamedSharding`, ``jax.jit`` the UNCHANGED pure
function, and let XLA's SPMD partitioner insert the collectives — no
hand-written ``psum`` anywhere on the compiled path, and the same
program text runs on 1 chip or 6000.

Two mechanisms coexist during the migration:

- **GSPMD (this module)** — serving AND the train step
  (``Model.compile(mesh=...)``): one jitted program over
  NamedSharding-annotated arrays. The train program's state shardings
  come from :func:`fit_state_spec` (and :func:`fsdp_state_spec` under
  ZeRO/FSDP), its batch inputs from the 'data' axis; XLA inserts the
  gradient all-reduces (or reduce-scatter/all-gather under FSDP).
- **shard_map + explicit collectives** (``communicator.py``,
  ``ops.py``, ``pipeline.py``) — the train step's LEGACY mechanism,
  still the default when ``compile`` is called without ``mesh=``. It
  remains the bitwise-parity reference the GSPMD path is pinned
  against, but it is a deprecation boundary: its layers announce their
  layouts through this module's spec vocabulary (so the two mechanisms
  can never disagree about what "column-parallel" means), and new
  sharded code should not add hand-rolled collectives.

Declines are TYPED, never silent: a config the mesh cannot honor
(heads that don't divide the model axis, a vocab that doesn't split, a
mesh smaller than the requested shards) raises
:class:`ShardingDecline` naming the offender — GSPMD would otherwise
fall back to replication and serve a "sharded" model that isn't.
"""

from __future__ import annotations

import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

BATCH_AXIS = "batch"
MODEL_AXIS = "model"
# the TRAINING batch axis (serving uses BATCH_AXIS; training meshes come
# from parallel.mesh whose dp axis has always been named 'data')
DATA_AXIS = "data"


class ShardingDecline(ValueError):
    """A sharding request the mesh cannot honor. Raised at build time,
    naming the offending dimension — never a silently replicated
    "sharded" program."""


# ---------------------------------------------------------------------------
# the spec vocabulary: every layer/serving rule speaks these
# ---------------------------------------------------------------------------

def replicated_spec():
    """Fully replicated (LN scale/bias, small biases, scalars)."""
    return P()


def col_spec(axis=MODEL_AXIS):
    """Column-parallel 2-D weight ``(in, out)``: OUT features sharded
    (Megatron column split — qkv projections, MLP up, LM head)."""
    return P(None, axis)


def col_bias_spec(axis=MODEL_AXIS):
    """Bias of a column-parallel layer: sharded like its out features."""
    return P(axis)


def row_spec(axis=MODEL_AXIS):
    """Row-parallel 2-D weight ``(in, out)``: IN features sharded
    (Megatron row split — attention out-proj, MLP down). The bias of a
    row-parallel layer is replicated (:func:`replicated_spec`)."""
    return P(axis, None)


def vocab_spec(axis=MODEL_AXIS):
    """Embedding table ``(vocab, d)``: vocab ROWS sharded — the
    input-side twin of a column-sharded LM head."""
    return P(axis, None)


def expert_spec(axis="expert"):
    """Expert-banked weight ``(E, ...)``: leading expert dim sharded
    over the expert-parallel axis."""
    return P(axis)


def batch_spec(axis=BATCH_AXIS, rank=1):
    """Leading-dim batch sharding for an activation/IO array of
    ``rank`` dims (slots, request rows, token batches)."""
    return P(axis, *([None] * (rank - 1)))


def fit_state_spec(spec, shape, mesh):
    """A parameter's announced PartitionSpec, with any dim that does not
    divide its mesh axes replicated instead (e.g. a vocab of 31 over
    'model'=2: the layer announces P('model', None) unconditionally
    because it cannot know the mesh at init; sharding such a dim would
    make shard_map reject the whole step, so the dim falls back to
    replication and the layers' offset math detects the full-width
    tensor). The checkpoint live-sharding template and the compiled
    step both resolve layouts through this ONE function."""
    if spec is None:
        return P()
    fitted = []
    for dim, names in enumerate(spec):
        if names is None:
            fitted.append(None)
            continue
        tup = names if isinstance(names, tuple) else (names,)
        size = 1
        for n in tup:
            size *= mesh.shape[n]
        fitted.append(names if dim < len(shape) and
                      shape[dim] % size == 0 else None)
    while fitted and fitted[-1] is None:
        fitted.pop()
    return P(*fitted)


def fsdp_state_spec(spec, shape, mesh, axis=DATA_AXIS):
    """ZeRO/FSDP layout for ONE param / optimizer-aux / master tensor:
    the announced spec (mesh-fitted through :func:`fit_state_spec`)
    with the first still-replicated dim that divides the ``axis``
    degree additionally sharded over it. Params never announce the
    data axis themselves, so this composes with tensor/expert layouts
    instead of double-sharding a dim. Scalars (step counter, loss
    scale) and tensors with no divisible dim stay replicated — an
    honest fallback, not a decline: FSDP is a memory layout, and a
    handful of tiny replicated leaves does not change the N× headroom
    the big buffers provide."""
    if axis not in mesh.shape:
        raise ShardingDecline(
            f"fsdp axis {axis!r} is not in the mesh "
            f"{dict(mesh.shape)}: build the train mesh with a "
            f"{axis!r} axis (parallel.mesh.MeshConfig names it)")
    base = fit_state_spec(spec, shape, mesh)
    deg = int(mesh.shape[axis])
    if deg <= 1 or not shape:
        return base
    entries = list(base) + [None] * (len(shape) - len(base))
    for dim, names in enumerate(entries):
        if names is None and shape[dim] % deg == 0:
            entries[dim] = axis
            break
    else:
        return base
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------

def serving_mesh(devices=None, model_shards=1, batch_shards=None):
    """A named ``(batch × model)`` serving mesh.

    ``model_shards`` tensor-parallel degree; ``batch_shards`` defaults
    to "every remaining device". Typed declines when the device count
    cannot cover the request."""
    import jax
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    m = int(model_shards)
    if m < 1:
        raise ShardingDecline(f"model_shards must be >= 1, got {m}")
    if m > n:
        raise ShardingDecline(
            f"model_shards={m} exceeds the {n} available devices: the "
            "mesh cannot be built — lower model_shards or add devices")
    if batch_shards:
        # an explicit batch degree only needs the device set to COVER
        # the mesh (trailing devices may idle — the caller chose)
        b = int(batch_shards)
        if b * m > n:
            raise ShardingDecline(
                f"batch_shards={b} × model_shards={m} exceeds the "
                f"{n} available devices")
    else:
        if n % m != 0:
            raise ShardingDecline(
                f"{n} devices do not divide into model_shards={m}: "
                "the default (batch × model) mesh must tile the "
                "device set exactly — pass batch_shards to use a "
                "subset deliberately")
        b = n // m
    arr = np.asarray(devices[:b * m]).reshape(b, m)
    return Mesh(arr, (BATCH_AXIS, MODEL_AXIS))


def train_mesh(devices=None, data=-1, model=1, stage=1):
    """A named training mesh over the (data × model × stage)
    vocabulary. ONE table with the shard_map world: ``stage`` binds to
    ``parallel.mesh``'s existing ``pipe`` axis name (pipeline stages),
    so pipeline layouts, ``elastic_mesh`` resharding, and checkpoint
    live-sharding all keep speaking the same axis names across the
    GSPMD migration. ``data=-1`` means "everything left" — the elastic
    default. Fully explicit degrees may use a leading device SUBSET
    (trailing devices idle — the caller chose, same contract as
    :func:`serving_mesh` with an explicit batch degree). Typed
    declines for device counts the degrees cannot tile."""
    import jax
    from . import mesh as mesh_mod
    if devices is None:
        devices = jax.devices()
    d, m, s = int(data), int(model), int(stage)
    if m < 1 or s < 1:
        raise ShardingDecline(
            f"model={m} / stage={s} degrees must be >= 1")
    n = len(devices)
    need = m * s * (d if d != -1 else 1)
    if need > n or n % (m * s) != 0:
        raise ShardingDecline(
            f"train mesh data={d} model={m} stage={s} cannot tile the "
            f"{n} available devices: degrees must cover the device "
            "set exactly")
    if d != -1:
        devices = list(devices)[:d * m * s]
    cfg = mesh_mod.MeshConfig(data=d, model=m, pipe=s)
    return mesh_mod.make_mesh(devices, cfg)


def serving_partitioner(mesh=None, model_shards=None, devices=None,
                        max_batch=None):
    """Resolve ``compile_serving(mesh=..., model_shards=...)`` into a
    :class:`Partitioner`. An explicit mesh must carry the named
    ``batch``/``model`` axes (extra axes must be size 1) and is taken
    as pinned — indivisible geometry against it refuses typed. With
    only ``model_shards`` a fresh mesh is built over the devices, its
    ``batch`` degree auto-fitted: the largest divisor of ``max_batch``
    (the engine passes its slot count) that the remaining devices
    cover, so a 2-slot engine on 8 chips gets a (2 × model) mesh
    instead of a refusal."""
    if mesh is None:
        import jax
        devs = devices if devices is not None else jax.devices()
        m = int(model_shards or 1)
        b = None
        if max_batch is not None and 1 <= m <= len(devs):
            # largest divisor of the slot count the remaining devices
            # cover: a 6-slot engine on 8 chips at model_shards=2 gets
            # batch=3 (6 devices), not gcd's 2
            fits = [d for d in range(1, int(max_batch) + 1)
                    if int(max_batch) % d == 0 and d * m <= len(devs)]
            b = max(fits) if fits else None
        return Partitioner(serving_mesh(
            devices=devs, model_shards=m, batch_shards=b))
    if not isinstance(mesh, Mesh):
        raise ShardingDecline(
            f"mesh must be a jax.sharding.Mesh, got {type(mesh).__name__}")
    if BATCH_AXIS not in mesh.shape or MODEL_AXIS not in mesh.shape:
        raise ShardingDecline(
            f"serving mesh needs named axes ({BATCH_AXIS!r}, "
            f"{MODEL_AXIS!r}); got {tuple(mesh.axis_names)}")
    extra = [a for a in mesh.axis_names
             if a not in (BATCH_AXIS, MODEL_AXIS) and mesh.shape[a] != 1]
    if extra:
        raise ShardingDecline(
            f"serving mesh has extra non-unit axes {extra}; only "
            f"{BATCH_AXIS!r} and {MODEL_AXIS!r} partition the serve "
            "programs")
    if model_shards and int(model_shards) != mesh.shape[MODEL_AXIS]:
        raise ShardingDecline(
            f"model_shards={model_shards} disagrees with the mesh's "
            f"'{MODEL_AXIS}' degree {mesh.shape[MODEL_AXIS]}")
    return Partitioner(mesh)


class Partitioner:
    """NamedSharding factory over one mesh: spec→sharding resolution,
    tree placement, divisibility checks, and per-device accounting."""

    def __init__(self, mesh, batch_axis=BATCH_AXIS,
                 model_axis=MODEL_AXIS):
        for ax in (batch_axis, model_axis):
            if ax not in mesh.shape:
                raise ShardingDecline(
                    f"mesh {dict(mesh.shape)} has no '{ax}' axis")
        self.mesh = mesh
        self.batch_axis = batch_axis
        self.model_axis = model_axis

    @property
    def batch_shards(self):
        return int(self.mesh.shape[self.batch_axis])

    @property
    def model_shards(self):
        return int(self.mesh.shape[self.model_axis])

    @property
    def n_devices(self):
        return int(np.prod(list(self.mesh.shape.values())))

    def describe(self):
        """The mesh stamp /healthz, heartbeats, and manifests carry."""
        return {"batch": self.batch_shards, "model": self.model_shards,
                "devices": self.n_devices}

    # -- spec resolution ----------------------------------------------------
    def sharding(self, spec):
        return NamedSharding(self.mesh, spec)

    def sharding_tree(self, spec_tree):
        """Same-structure tree of NamedShardings (PartitionSpec leaves)."""
        import jax
        return jax.tree_util.tree_map(
            self.sharding, spec_tree,
            is_leaf=lambda x: isinstance(x, P))

    def shard(self, tree, spec_tree):
        """device_put every leaf onto its NamedSharding — the one
        placement chokepoint for params and KV state."""
        import jax
        import jax.numpy as jnp
        return jax.tree_util.tree_map(
            lambda a, s: jax.device_put(jnp.asarray(a),
                                        self.sharding(s)),
            tree, spec_tree,
            is_leaf=lambda x: isinstance(x, P))

    # -- typed declines -----------------------------------------------------
    def require_divisible(self, what, size, axis=None):
        """``size % axis-degree == 0`` or a :class:`ShardingDecline`
        naming the offender — the guard that keeps "sharded" honest
        (GSPMD would silently replicate an indivisible dim)."""
        axis = axis or self.model_axis
        deg = int(self.mesh.shape[axis])
        if int(size) % deg != 0:
            raise ShardingDecline(
                f"{what} = {size} does not divide the '{axis}' mesh "
                f"axis (degree {deg}): the mesh cannot shard it — "
                "XLA would silently replicate instead, so this config "
                "is refused")

    # -- accounting ---------------------------------------------------------
    @staticmethod
    def per_device_bytes(tree):
        """Per-device bytes of a (possibly sharded) array tree — what
        one chip actually holds, the honest HBM number for fleet
        gauges. Unsharded arrays count full size."""
        import jax
        total = 0
        for a in jax.tree_util.tree_leaves(tree):
            shape = tuple(a.shape)
            sh = getattr(a, "sharding", None)
            if sh is not None and hasattr(sh, "shard_shape"):
                shape = sh.shard_shape(shape)
            total += int(np.prod(shape, dtype=np.int64)) * \
                np.dtype(a.dtype).itemsize
        return int(total)

    @staticmethod
    def global_bytes(tree):
        import jax
        return int(sum(
            int(np.prod(a.shape, dtype=np.int64)) *
            np.dtype(a.dtype).itemsize
            for a in jax.tree_util.tree_leaves(tree)))


# ---------------------------------------------------------------------------
# serving rule tables: the LM decode-param tree, ring caches, block pools
# ---------------------------------------------------------------------------

def _weight_entry_spec(w, spec):
    """Spec for one weight entry of the serve param tree: a float array
    gets ``spec`` directly; an int8 weight-only payload ``{"q","s"}``
    shards the payload like the weight and its rank-preserving
    per-out-channel scale along the same out axis."""
    if isinstance(w, dict):
        # scale keeps the payload's rank (quant.core.quantize_int8), so
        # it shards along exactly the axes the payload does that it has
        # size > 1 in; for the (1, out) 2-D scales that is the out axis.
        s_spec = P(*[ax if int(d) > 1 else None
                     for ax, d in zip(tuple(spec) +
                                      (None,) * len(w["s"].shape),
                                      w["s"].shape)])
        return {"q": spec, "s": s_spec}
    return spec


def lm_param_specs(part, params, n_heads):
    """PartitionSpec tree for the transformer serve-param dict
    (``models.transformer._lm_decode_params`` layout): attention heads
    and MLP hidden split over ``model``, vocab-sharded embedding rows
    and head columns, everything small replicated. ``blocks`` holds
    its layers role by role: a vector role is one leaf stacked over the
    layers and gets its spec behind a ``None`` for the layer axis, a
    matrix role is a list with the role's spec a layer. Typed declines
    for every dimension the mesh cannot split honestly."""
    ax = part.model_axis
    part.require_divisible("n_heads", n_heads, ax)
    vocab = int(params["tok"].shape[0])
    part.require_divisible("vocab_size", vocab, ax)
    p = params["blocks"]
    if "wg" in p:
        raise ShardingDecline(
            "MoE decode blocks are not mesh-shardable yet: the "
            "expert banks would silently replicate per device; serve "
            "MoE models single-device, or train with the 'expert' "
            "axis")
    w_up = p["w_up"][0]
    d_ff = int((w_up["q"] if isinstance(w_up, dict) else w_up).shape[1])
    part.require_divisible("d_ff (MLP hidden)", d_ff, ax)

    def vector(spec):
        return P(None, *spec)

    def matrix(role, spec):
        return [_weight_entry_spec(w, spec) for w in p[role]]

    blocks = {
        "ln1_s": vector(P()), "ln1_b": vector(P()),
        "ln2_s": vector(P()), "ln2_b": vector(P()),
        # qkv columns = heads × head_dim: whole heads per shard
        # (n_heads % m checked above keeps the reshape honest)
        "wq": matrix("wq", col_spec(ax)),
        "bq": vector(col_bias_spec(ax)),
        "wk": matrix("wk", col_spec(ax)),
        "bk": vector(col_bias_spec(ax)),
        "wv": matrix("wv", col_spec(ax)),
        "bv": vector(col_bias_spec(ax)),
        "wo": matrix("wo", row_spec(ax)),
        "bo": vector(P()),
        "w_up": matrix("w_up", col_spec(ax)),
        "b_up": vector(col_bias_spec(ax)),
        "w_dn": matrix("w_dn", row_spec(ax)),
        "b_dn": vector(P()),
    }
    return dict(
        tok=vocab_spec(ax),          # vocab rows sharded
        pos=P(),                     # tiny, every rank reads every row
        lnf_s=P(), lnf_b=P(),
        head_w=col_spec(ax),         # vocab columns sharded
        head_b=col_bias_spec(ax),
        blocks=blocks)


def ring_cache_specs(part, cache):
    """Ring KV levels ``(W, H, L, D)``: slots over ``batch``, heads
    over ``model``; int8 scale rows ``(W, L)`` ride the slot axis."""
    out = []
    for level in cache:
        spec = {"k": P(part.batch_axis, part.model_axis, None, None),
                "v": P(part.batch_axis, part.model_axis, None, None)}
        if "k_scale" in level:
            spec["k_scale"] = P(part.batch_axis, None)
            spec["v_scale"] = P(part.batch_axis, None)
        out.append(spec)
    return out


def pool_specs(part, pool):
    """Paged KV pools ``(N, H, bs, D)``: heads over ``model``, blocks
    REPLICATED over ``batch`` — prefix-shared blocks are referenced by
    slots on every batch shard, so the pool is per-device-whole with a
    per-device head slice (the per-chip HBM win is H/model_shards);
    int8 scale planes ``(N, bs)`` are head-less, hence replicated."""
    out = []
    for level in pool:
        spec = {"k": P(None, part.model_axis, None, None),
                "v": P(None, part.model_axis, None, None)}
        if "k_scale" in level:
            spec["k_scale"] = P()
            spec["v_scale"] = P()
        out.append(spec)
    return out


def serving_arg_specs(part, kv_layout):
    """PartitionSpecs for the serve programs' HOST-ARRAY arguments and
    token outputs, per KV layout.

    Decode's per-slot rows ride the ``batch`` axis (``slots`` divides
    it — checked at engine build); prefill's small fixed-width batch
    arrays are replicated (``prefill_batch`` need not divide the mesh,
    and a handful of prompt rows is not where sharding pays). Token
    outputs are replicated — the host scheduler reads every slot's
    token each tick."""
    b = part.batch_axis
    if kv_layout == "paged":
        return {
            # (tables, tokens, starts, lengths, valid)
            "prefill": (P(), P(), P(), P(), P()),
            # (tables (W,n_pages), tokens (W,K), positions, counts)
            "decode": (P(b, None), P(b, None), P(b), P(b)),
            "tokens_out": P(),
        }
    return {
        # (tokens, lengths, slot_ids, valid)
        "prefill": (P(), P(), P(), P()),
        # (prev_tokens (W,): the last call's tokens_out, tokens (W,),
        #  fresh (W,), positions (W,), active (W,))
        "decode": (P(), P(b), P(b), P(b), P(b)),
        "tokens_out": P(),
    }


__all__ = ["BATCH_AXIS", "MODEL_AXIS", "DATA_AXIS", "ShardingDecline",
           "replicated_spec", "col_spec", "col_bias_spec", "row_spec",
           "vocab_spec", "expert_spec", "batch_spec", "fit_state_spec",
           "fsdp_state_spec", "serving_mesh", "train_mesh",
           "serving_partitioner", "Partitioner",
           "lm_param_specs", "ring_cache_specs", "pool_specs",
           "serving_arg_specs"]
