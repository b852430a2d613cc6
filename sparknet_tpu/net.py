"""JaxNet — the net compiler: NetParameter -> pure jitted functions.

This is the TPU-native replacement for the whole reference engine stack
``Net<Dtype>`` + ``Solver``'s forward path (``caffe/src/caffe/net.cpp``) and
for the Scala-side ``CaffeNet`` facade (``src/main/scala/libs/Net.scala``):

- ``Net::Init`` (DAG build, phase filter, param sharing by name at
  ``net.cpp:470``)  ->  ``JaxNet.__init__`` (static shape walk + blob init)
- ``Net::ForwardFromTo`` layer loop  ->  ``JaxNet.apply`` — a pure function
  of (params, stats, batch, rng) traced once under ``jit``; XLA fuses the
  layer chain, so there is no per-layer dispatch at run time
- data/diff twin blobs + ``Net::Update``  ->  gradients are values from
  ``jax.grad``; no mutable state anywhere
- ``getData``/``getWeights``/``setWeights`` float-copy loops
  (``Net.scala:131-191``)  ->  zero-copy pytrees of device arrays

Params layout parity: ``params[layer_name] == [weight, bias, ...]`` ordered
exactly like the reference layer's ``blobs_`` vector, with shared params
stored once under the owning layer (ParamSpec.name sharing).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from sparknet_tpu.config.schema import NetParameter, NetState
from sparknet_tpu.graph import filter_net, toposort_check
from sparknet_tpu.ops import fillers  # noqa: F401  (registry population)
from sparknet_tpu.ops import attention, common, data_layers, losses, vision  # noqa: F401
from sparknet_tpu.ops.base import BlobDef, Layer, create_layer

Params = Dict[str, List[jax.Array]]
Stats = Dict[str, List[jax.Array]]

# Sibling convolutions fuse in groups of at least this many (JaxNet.
# _plan_hconv).  Measured on the v5e: groups of three or more (GoogLeNet's
# Inception branches) +6.2% throughput; ResNet-50's two-member stage-entry
# pairs -3.9%, the concat and slices costing more than the wider tiling
# gains (ROADMAP.md, north star).
HCONV_MIN_MEMBERS = 3


@dataclasses.dataclass
class _BlobRef:
    """Where one layer blob lives: (collection, owner layer, index)."""

    collection: str  # "params" | "stats"
    owner: str
    index: int


@dataclasses.dataclass
class NetOutputs:
    blobs: Dict[str, jax.Array]
    loss: jax.Array
    stats: Stats


def layer_scope(lp) -> str:
    """``<Type>:<name>``, the ``jax.named_scope`` of one layer's work in the
    compiled program (its backward pass reads ``transpose(jvp(<Type>:<name>))``
    in the device trace).  The name stack is joined with ``/``, so a ``/`` in
    the layer's name (``inception_3a/1x1``) is written ``.``."""
    return f"{lp.type}:{lp.name.replace('/', '.')}"


class JaxNet:
    """A compiled net for one phase.

    Parameters
    ----------
    net_param:
        The (unfiltered) NetParameter; phase filtering happens here.
    phase:
        "TRAIN" or "TEST".
    feed_shapes:
        Extra {top_name: shape} for host-fed data layers that don't declare
        shapes inline.
    """

    def __init__(
        self,
        net_param: NetParameter,
        phase: str = "TRAIN",
        feed_shapes: Optional[Dict[str, Sequence[int]]] = None,
        stages: Sequence[str] = (),
        level: int = 0,
        compute_dtype: Optional[str] = None,
    ):
        # compute_dtype="bfloat16" runs layer compute in bf16 (params stay
        # f32 master copies; loss layers upcast to f32) — the TPU-native
        # mixed-precision recipe. None keeps full f32 (reference numerics).
        self.compute_dtype = (
            jnp.dtype(compute_dtype) if compute_dtype else None
        )
        self.phase = phase.upper()
        state = NetState(phase=self.phase, level=level, stage=list(stages))
        self.net_param = filter_net(net_param, state)
        self.name = self.net_param.name
        feed_shapes = {k: tuple(map(int, v)) for k, v in (feed_shapes or {}).items()}

        # net-level `input:` declarations are host-fed blobs too
        for i, blob in enumerate(self.net_param.input):
            if blob not in feed_shapes:
                if i < len(self.net_param.input_shape):
                    feed_shapes[blob] = tuple(
                        int(d) for d in self.net_param.input_shape[i].dim
                    )
                elif len(self.net_param.input_dim) >= 4 * (i + 1):
                    feed_shapes[blob] = tuple(
                        self.net_param.input_dim[4 * i : 4 * i + 4]
                    )
        toposort_check(self.net_param, external_tops=list(feed_shapes))

        self.layers: List[Layer] = []
        self.blob_shapes: Dict[str, Tuple[int, ...]] = dict(feed_shapes)
        self.feed_blobs: List[str] = list(feed_shapes)
        self._blob_defs: Dict[str, List[BlobDef]] = {}
        self._blob_refs: Dict[str, List[_BlobRef]] = {}
        self._loss_weights: Dict[str, List[float]] = {}
        param_owners: Dict[str, _BlobRef] = {}  # ParamSpec.name -> ref

        counts: Dict[str, int] = {}
        for lp in self.net_param.layer:
            layer = create_layer(lp, self.phase)
            if layer.name in counts:
                raise ValueError(f"duplicate layer name {layer.name!r}")
            counts[layer.name] = 1
            bshapes = [self.blob_shapes[b] for b in lp.bottom]

            if isinstance(layer, data_layers._HostFed):
                declared = layer.declared_shapes()
                tshapes = []
                for i, top in enumerate(lp.top):
                    if declared is not None and i < len(declared):
                        shape = declared[i]
                    elif top in feed_shapes:
                        shape = feed_shapes[top]
                    else:
                        raise ValueError(
                            f"data layer {layer.name!r}: no shape for top "
                            f"{top!r}; pass feed_shapes"
                        )
                    tshapes.append(tuple(shape))
                    self.feed_blobs.append(top)
            else:
                tshapes = layer.out_shapes(bshapes)

            defs = layer.blob_defs(bshapes)
            refs: List[_BlobRef] = []
            pi = si = 0
            for bi, d in enumerate(defs):
                spec = lp.param[bi] if bi < len(lp.param) else None
                shared_name = spec.name if spec and spec.name else None
                if shared_name and shared_name in param_owners:
                    owner_ref = param_owners[shared_name]
                    owner_defs = self._blob_defs[owner_ref.owner]
                    mode = (spec.share_mode or "STRICT").upper()
                    if mode == "STRICT" and tuple(
                        owner_defs[owner_ref.index].shape
                    ) != tuple(d.shape):
                        raise ValueError(
                            f"shared param {shared_name!r}: shape mismatch "
                            f"{owner_defs[owner_ref.index].shape} vs {d.shape}"
                        )
                    refs.append(owner_ref)
                else:
                    coll = "params" if d.learnable else "stats"
                    ref = _BlobRef(coll, layer.name, pi if d.learnable else si)
                    if d.learnable:
                        pi += 1
                    else:
                        si += 1
                    refs.append(ref)
                    if shared_name:
                        param_owners[shared_name] = ref

            self._blob_defs[layer.name] = defs
            self._blob_refs[layer.name] = refs
            self._loss_weights[layer.name] = layer.loss_weights()
            for top, shape in zip(lp.top, tshapes):
                self.blob_shapes[top] = tuple(int(x) for x in shape)
            self.layers.append(layer)

        # dedupe feed blobs, preserve order
        seen = set()
        self.feed_blobs = [
            b for b in self.feed_blobs if not (b in seen or seen.add(b))
        ]

        self._plan_hconv()

    def _plan_hconv(self) -> None:
        """Horizontal convolution fusion: sibling Convolution layers
        reading the *same* bottom with identical geometry (the Inception
        pattern — 1x1, 3x3-reduce and 5x5-reduce branches all read the
        block input) execute as ONE convolution whose output channels are
        the members' concatenated, then split back to the named tops.
        Each small conv tiles the 128x128 MXU poorly and re-reads the
        input from HBM; the fused conv does one read and one large
        contraction.  Only groups of ``HCONV_MIN_MEMBERS`` or more fuse.
        Parameters stay per-layer (concat happens inside the step), so
        checkpoints, weight import and the blob map are unchanged."""
        self._hconv_groups: Dict[int, dict] = {}
        self._hconv_skip: set = set()
        groups: Dict[tuple, List[int]] = {}
        for li, layer in enumerate(self.layers):
            lp = layer.lp
            if lp.type != "Convolution" or len(lp.bottom) != 1:
                continue
            cp = lp.convolution_param
            if max(1, cp.group) != 1:
                continue
            if any(self._loss_weights[layer.name]):
                continue
            try:
                geom = layer._geometry(self.blob_shapes[lp.bottom[0]])
            except Exception:
                continue
            key = (lp.bottom[0], geom, bool(cp.bias_term))
            groups.setdefault(key, []).append(li)
        for key, lis in groups.items():
            if len(lis) < HCONV_MIN_MEMBERS:
                continue
            bottom = key[0]
            # executing every member at the leader's slot must not change
            # what anything reads.  Two hazards: (a) a layer in the fused
            # span rewrites the shared bottom in place — members would
            # read different versions of it; (b) a member's top name is
            # produced or read by some layer between the leader and that
            # member's original slot (legal top-name rebinding,
            # graph.py toposort) — early production would change what
            # that layer sees.  Layers at/after a member's original slot
            # are unaffected (production only moves earlier).
            if any(
                bottom in self.layers[mid].lp.top
                for mid in range(lis[0], lis[-1] + 1)
            ):
                continue
            hazard = False
            for li in lis[1:]:
                t = self.layers[li].lp.top[0]
                for mid in range(lis[0], li):
                    lm = self.layers[mid].lp
                    if t in lm.top or t in lm.bottom:
                        hazard = True
                        break
                if hazard:
                    break
            if hazard:
                continue
            leader = lis[0]
            self._hconv_groups[leader] = {
                "lis": lis,
                "geom": key[1],
                "bias": key[2],
                # each member's own num_output — NOT blob_shapes[top],
                # which holds the final binding of a possibly-rebound name
                "sizes": [
                    self.layers[li].lp.convolution_param.num_output
                    for li in lis
                ],
            }
            self._hconv_skip.update(lis[1:])

    def _apply_hconv(self, group, x, params, perturb, blobs) -> None:
        """Run one fused sibling-conv group and write every member top."""
        (kh, kw), (sh, sw), (ph, pw), (dh, dw) = group["geom"]
        members = [self.layers[li] for li in group["lis"]]
        gathered = [self._gather_blobs(m.name, params, {}) for m in members]
        cd = self.compute_dtype
        if cd is not None and jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(cd)
            gathered = [[b.astype(cd) for b in g] for g in gathered]
        w = jnp.concatenate([g[0] for g in gathered], axis=0)
        y = jax.lax.conv_general_dilated(
            x,
            w,
            window_strides=(sh, sw),
            padding=[(ph, ph), (pw, pw)],
            rhs_dilation=(dh, dw),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )
        if group["bias"]:
            b = jnp.concatenate([g[1] for g in gathered])
            y = y + b.reshape(1, -1, 1, 1)
        off = 0
        for m, size in zip(members, group["sizes"]):
            top = m.lp.top[0]
            out = jax.lax.slice_in_dim(y, off, off + size, axis=1)
            off += size
            if perturb is not None and top in perturb:
                out = out + perturb[top]
            blobs[top] = out

    # ------------------------------------------------------------------
    # Introspection (the `num_layers`/`layer_names`/blob enumeration side
    # of the engine API, ccaffe.h:30-45)
    # ------------------------------------------------------------------
    @property
    def layer_names(self) -> List[str]:
        return [l.name for l in self.layers]

    def param_multipliers(self) -> Tuple[Params, Params]:
        """Per-blob (lr_mult, decay_mult) pytrees matching init() params
        structure (reference: ParamSpec handling in ``net.cpp
        AppendParam``)."""
        lr: Dict[str, List[float]] = {}
        decay: Dict[str, List[float]] = {}
        for layer in self.layers:
            for d, ref in zip(
                self._blob_defs[layer.name], self._blob_refs[layer.name]
            ):
                if ref.collection == "params" and ref.owner == layer.name:
                    lr.setdefault(layer.name, []).append(d.lr_mult)
                    decay.setdefault(layer.name, []).append(d.decay_mult)
        return lr, decay

    # ------------------------------------------------------------------
    # Init
    # ------------------------------------------------------------------
    def init(self, seed: int = 0) -> Tuple[Params, Stats]:
        """Initialize all blobs with their fillers (Net::Init's filler pass)."""
        key = jax.random.PRNGKey(seed)
        params: Params = {}
        stats: Stats = {}
        for li, layer in enumerate(self.layers):
            defs = self._blob_defs[layer.name]
            refs = self._blob_refs[layer.name]
            if not defs:
                continue
            lkey = jax.random.fold_in(key, li)
            keys = jax.random.split(lkey, len(defs))
            for d, ref, k in zip(defs, refs, keys):
                if ref.owner != layer.name:
                    continue  # shared: owner already initialized it
                arr = fillers.fill(k, d.shape, d.filler)
                if ref.collection == "params":
                    params.setdefault(layer.name, []).append(arr)
                else:
                    stats.setdefault(layer.name, []).append(arr)
        return params, stats

    # ------------------------------------------------------------------
    # Execution
    # ------------------------------------------------------------------
    def _gather_blobs(self, layer_name: str, params: Params, stats: Stats):
        out = []
        for ref in self._blob_refs[layer_name]:
            coll = params if ref.collection == "params" else stats
            out.append(coll[ref.owner][ref.index])
        return out

    def apply(
        self,
        params: Params,
        stats: Stats,
        batch: Dict[str, jax.Array],
        rng: Optional[jax.Array] = None,
        train: Optional[bool] = None,
        perturb: Optional[Dict[str, jax.Array]] = None,
    ) -> NetOutputs:
        """Run the net. Returns every named blob (the ``getData`` analog,
        Net.scala:173-191), the weighted total loss, and updated stats.

        ``perturb`` adds a zero-valued tensor to each named top as it is
        produced — differentiating w.r.t. those taps yields every
        activation gradient in one backward pass (the diff side of the
        reference's data/diff twin blobs; used by ``Solver.debug_info_pass``,
        net.cpp:648-735)."""
        train = (self.phase == "TRAIN") if train is None else train
        blobs: Dict[str, jax.Array] = {}
        for b in self.feed_blobs:
            if b not in batch:
                raise ValueError(f"batch missing feed blob {b!r}")
            blobs[b] = jnp.asarray(batch[b])
        new_stats: Stats = {k: list(v) for k, v in stats.items()}
        loss = jnp.asarray(0.0, jnp.float32)

        cd = self.compute_dtype
        for li, layer in enumerate(self.layers):
            lp = layer.lp
            if li in self._hconv_skip:
                continue
            # everything the layer causes (casts, apply, loss term) under
            # one scope: the device trace reads time per layer from it
            with jax.named_scope(layer_scope(lp)):
                if li in self._hconv_groups:
                    self._apply_hconv(
                        self._hconv_groups[li], blobs[lp.bottom[0]], params,
                        perturb, blobs,
                    )
                    continue
                if isinstance(layer, data_layers._HostFed):
                    # host blobs keep their dtype: index-valued blobs (labels)
                    # must never round through bf16; consumers cast as needed
                    tops = [blobs[t] for t in lp.top]
                else:
                    lblobs = self._gather_blobs(layer.name, params, new_stats)
                    bottoms = [blobs[b] for b in lp.bottom]
                    if cd is not None:
                        if layer.IS_LOSS:
                            # losses compute in f32 for stable log/exp; the
                            # label bottom is f32 already (exact indices)
                            bottoms = [b.astype(jnp.float32) for b in bottoms]
                        elif not layer.MIXED_PRECISION_EXEMPT:
                            lblobs = [b.astype(cd) for b in lblobs]
                            bottoms = [
                                b.astype(cd)
                                if jnp.issubdtype(b.dtype, jnp.floating)
                                else b
                                for b in bottoms
                            ]
                    lrng = (
                        jax.random.fold_in(rng, li) if rng is not None
                        else None
                    )
                    tops, updated = layer.apply(lblobs, bottoms, lrng, train)
                    if updated is not None:
                        refs = self._blob_refs[layer.name]
                        for d, ref, arr in zip(
                            self._blob_defs[layer.name], refs, updated
                        ):
                            if ref.collection == "stats":
                                # keep stat blobs at their master dtype even
                                # under bf16 compute
                                cur = new_stats[ref.owner][ref.index]
                                new_stats[ref.owner][ref.index] = arr.astype(
                                    cur.dtype
                                )
                if perturb is not None:
                    tops = [
                        top + perturb[name] if name in perturb else top
                        for name, top in zip(lp.top, tops)
                    ]
                for w, top, name in zip(
                    self._loss_weights[layer.name], tops, lp.top
                ):
                    if w:
                        loss = loss + w * jnp.sum(top)
                for name, top in zip(lp.top, tops):
                    blobs[name] = top
        return NetOutputs(blobs=blobs, loss=loss, stats=new_stats)

    def forward(
        self,
        params: Params,
        stats: Stats,
        batch: Dict[str, jax.Array],
        rng: Optional[jax.Array] = None,
    ) -> Dict[str, jax.Array]:
        """Inference forward returning all blobs (FeaturizerApp's
        forward+getData path, FeaturizerApp.scala:88-103)."""
        return self.apply(params, stats, batch, rng=rng, train=False).blobs

    def loss_fn(self, params, stats, batch, rng=None, train=True):
        """(loss, (blobs, stats)) — the function handed to ``jax.grad``."""
        out = self.apply(params, stats, batch, rng=rng, train=train)
        return out.loss, (out.blobs, out.stats)
