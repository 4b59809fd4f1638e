"""Update compression for the constrained link (client->server uploads and
cross-pod outer syncs) — plane-resident.

Each compressor is (compress, decompress, error-feedback) over a pytree of
deltas. Compression is *lossy + error-fed-back*: the residual left behind
by compression is accumulated locally and added to the next round's delta
(Seide et al. 1-bit SGD trick) so the long-run bias vanishes.

The hot path is the PLANE formulation (``compress_plane``): deltas arrive
stacked ``[R, ...]`` (one row per delivering client — or per (scenario,
client) slot in a grid sweep), the error-feedback residuals live in a
``[N_clients, ...]`` device-resident pytree, and one donated jit gathers
the delivering rows' residuals, compresses, and scatters the new residuals
back. No per-client Python loop, no host round-trip — compressed rounds
stay on the stacked engine at full speed. The sequential API (``compress``/
``decompress``) is built from the SAME row primitives with R=1, so the two
paths are bitwise identical at equal inputs (the parity contract the
batched server and the grid engine's provenance coalescing rely on).

Row math: top-k is ``jax.lax.top_k`` over flattened rows; int8 and bf16
route through the Pallas ``kernels/quantize.py`` row kernels on TPU and an
identical one-pass XLA reference elsewhere. int8 rounding is deterministic
round-half-up (not stochastic): determinism is what lets compressed sweep
points share provenance, and error feedback already removes the long-run
bias of any fixed rounding rule.

``wire_bytes`` reports exact per-leaf wire size — fed into the transport
model so the paper-figure benchmarks account for compression x network
interplay, and into the cross-pod roofline's collective-bytes estimate.

``fingerprint`` is the hashable identity of the compression semantics:
two compressors with equal fingerprints map equal (delta, residual) to
equal (decompressed, new residual). The grid engine folds it — together
with a residual-provenance digest — into its coalescing keys so compressed
sweep points regain row sharing. An empty fingerprint (stateful randk)
marks the compressor opaque.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kernel_ops
from repro.kernels import ref as kernel_ref
from repro.utils import tree_size


@dataclass(frozen=True)
class Compressor:
    """One update-compression scheme; see the module docstring for the
    plane/sequential parity contract.

    Payload-bytes convention: ``wire_bytes(tree)`` is the EXACT upload
    wire size of one compressed update shaped like ``tree`` — the number
    every transport engine bills for the client->server direction, while
    downloads bill the full model (``LocalTask.update_bytes``). The grid
    driver also stamps it per scenario row into ``sim_grid_round``'s
    ``update_bytes`` plane, so compression x network interplay is exact
    per sweep point."""

    name: str
    compress: Callable  # (delta, residual) -> (payload, new_residual)
    decompress: Callable  # payload -> delta (same tree structure as input)
    wire_bytes: Callable  # (tree_template) -> int
    # Plane twin: (stacked_delta [R,...], residual_buffer [K,...], rows [R])
    #   -> (decompressed stacked [R,...], new residual_buffer). One donated
    # jit; ``rows`` are PHYSICAL buffer rows. Under the dense StatePlane
    # K == N_clients and rows == client slots (the PR-4 layout); under the
    # sparse plane K is the compacted capacity and the caller maps slots
    # to rows via ``StatePlane.rows_for`` first. The programs are
    # index-agnostic either way. None => the server falls back to the
    # sequential per-client loop.
    compress_plane: Optional[Callable] = None
    # Hashable semantics identity for provenance coalescing; () => opaque.
    fingerprint: tuple = ()
    # Host-side state accessors for the round-boundary checkpoint protocol:
    # state_get() -> JSON-safe snapshot, state_set(snapshot) -> None.
    # Stateful compressors (randk's rotating draw counter) expose these so
    # killed runs resume bitwise; both None => the compressor is stateless
    # on the host and checkpoints need save nothing.
    state_get: Optional[Callable] = None
    state_set: Optional[Callable] = None


def init_residual_plane(template, n: int):
    """Zero residual plane: one f32 row per client, template-shaped leaves.

    This is the DENSE layout — ``repro.core.stateplane.StatePlane`` wraps
    it (storage="dense") and adds the compacted sparse alternative; the
    plane programs below consume either buffer unchanged."""
    return jax.tree.map(
        lambda l: jnp.zeros((n,) + l.shape, jnp.float32), template
    )


def _leafwise(delta, residual, one):
    """Apply ``one(d, r) -> (payload_leaf, new_residual_leaf)`` leaf-wise."""
    leaves_d, treedef = jax.tree.flatten(delta)
    leaves_r = (
        treedef.flatten_up_to(residual)
        if residual is not None
        else [None] * len(leaves_d)
    )
    pairs = [one(d, r) for d, r in zip(leaves_d, leaves_r)]
    payload = jax.tree.unflatten(treedef, [p[0] for p in pairs])
    new_res = jax.tree.unflatten(treedef, [p[1] for p in pairs])
    return payload, new_res


def _plane_compress_fn(row_fn):
    """Lift a per-leaf row transform ``row_fn(x2 [R, n]) -> deq2 [R, n]``
    into the plane compressor.

    Three programs, not one, for two reasons:

    - The residual subtraction must consume the ROUNDED dequantized
      buffer. In a single program XLA fuses the dequantize multiply into
      ``x2 - deq2`` as an FMA (even across an optimization barrier), so
      the residual would see the unrounded product and drift one ulp from
      the sequential per-client path — breaking the bitwise parity the
      grid's provenance coalescing keys on.
    - The heavy middle program (``compress_rows``) is a pure function of
      (stacked deltas, residual rows) — no plane state — so the grid
      engine MEMOIZES it across sweep points whose compression provenance
      coincides; only the cheap gather/scatter run per point.

    The residual plane is DONATED into the scatter program: XLA reuses its
    buffers instead of allocating a second model-times-clients copy per
    round. The pieces are exposed as attributes on the returned function
    (``gather_rows`` / ``compress_rows`` / ``scatter_rows`` /
    ``finalize``) for callers that orchestrate sharing themselves.
    """

    @jax.jit
    def gather_rows(residual_plane, slots):
        return jax.tree.map(lambda res: jnp.take(res, slots, axis=0), residual_plane)

    @jax.jit
    def compress_rows(stacked, residual_rows):
        def one(d, res_rows):
            r = d.shape[0]
            x2 = d.astype(jnp.float32).reshape(r, -1) + res_rows.reshape(r, -1)
            return x2, row_fn(x2)

        return _leafwise(stacked, residual_rows, one)

    @functools.partial(jax.jit, donate_argnums=(2,))
    def scatter_rows(x2_tree, deq_tree, residual_plane, slots):
        def one(x2, deq2, res):
            new_rows = (x2 - deq2).reshape((x2.shape[0],) + res.shape[1:])
            return res.at[slots].set(new_rows)

        return jax.tree.map(one, x2_tree, deq_tree, residual_plane)

    def finalize(stacked, deq_tree):
        return jax.tree.map(
            lambda d, q2: q2.reshape(d.shape).astype(d.dtype), stacked, deq_tree
        )

    def compress_plane(stacked, residual_plane, slots):
        slots = jnp.asarray(slots, jnp.int32)
        rows = gather_rows(residual_plane, slots)
        x2_tree, deq_tree = compress_rows(stacked, rows)
        new_res = scatter_rows(x2_tree, deq_tree, residual_plane, slots)
        return finalize(stacked, deq_tree), new_res

    compress_plane.gather_rows = gather_rows
    compress_plane.compress_rows = compress_rows
    compress_plane.scatter_rows = scatter_rows
    compress_plane.finalize = finalize
    return compress_plane


def _sparse_wire_bytes(ratio: float):
    """Exact sparse wire size: 4B idx + 4B val per kept coordinate, per
    leaf (each leaf keeps max(n*ratio, 1) coordinates — the same k the
    row math uses)."""

    def wire_bytes(t):
        return int(
            sum(
                8 * max(int(np.prod(l.shape, dtype=np.int64) * ratio), 1)
                for l in jax.tree.leaves(t)
            )
        )

    return wire_bytes


# ---------------------------------------------------------------------------
# row primitives (shared by the sequential R=1 and plane [R, n] paths)
# ---------------------------------------------------------------------------


def _topk_rows(x2, ratio: float):
    """Magnitude top-k per row: returns (sparse [R, n], idx [R, k], kept)."""
    n = x2.shape[-1]
    k = max(int(n * ratio), 1)
    _, idx = jax.lax.top_k(jnp.abs(x2), k)
    kept = jnp.take_along_axis(x2, idx, axis=-1)
    rows = jnp.arange(x2.shape[0])[:, None]
    sparse = jnp.zeros_like(x2).at[rows, idx].set(kept)
    return sparse, idx, kept


def _int8_rows(x2):
    """Symmetric per-row int8: returns (deq2 [R, n], q int8, scale [R]).

    Kernel-backed on TPU (Pallas ``quantize_rows``); off-TPU the identical
    round-half-up math runs as one fused XLA pass (interpret-mode Pallas is
    several times slower than XLA, so CI never pays the interpreter on the
    server hot path — tests assert kernel == reference separately).

    The scale multiplies by 1/127 rather than dividing by 127: XLA rewrites
    a division by a constant into that multiplication inside a jit but not
    in an eager op, so only the product is the same in the plane program
    and the eager sequential path.
    """
    scale = jnp.maximum(jnp.max(jnp.abs(x2), axis=-1), 1e-12) * (1.0 / 127.0)
    if kernel_ops.default_interpret():
        q = kernel_ref.quantize_rows_ref(x2, scale)
    else:
        q = kernel_ops.quantize_rows(x2, scale, interpret=False)
    return q.astype(jnp.float32) * scale[:, None], q, scale


def _bf16_rows(x2):
    """bf16 downcast per row: returns (deq2 [R, n] f32, b bf16)."""
    if kernel_ops.default_interpret():
        b = kernel_ref.downcast_bf16_rows_ref(x2)
    else:
        b = kernel_ops.downcast_bf16_rows(x2, interpret=False)
    return b.astype(jnp.float32), b


# ---------------------------------------------------------------------------
# compressors
# ---------------------------------------------------------------------------


def none_compressor() -> Compressor:
    return Compressor(
        "none",
        lambda d, r: (d, r),
        lambda p: p,
        lambda t: 4 * tree_size(t),
        fingerprint=("none",),
    )


def topk_compressor(ratio: float = 0.01) -> Compressor:
    """Per-leaf magnitude top-k with error feedback (stacked lax.top_k)."""

    def compress(delta, residual):
        def one(d, r):
            x = d.astype(jnp.float32) + (
                r.astype(jnp.float32) if r is not None else 0.0
            )
            x2 = x.reshape(1, -1)
            sparse, idx, kept = _topk_rows(x2, ratio)
            new_r = (x2 - sparse).reshape(d.shape)
            return {"idx": idx[0], "vals": kept[0], "shape": d.shape}, new_r

        return _leafwise(delta, residual, one)

    def decompress(payload):
        def one(p):
            n = 1
            for s in p["shape"]:
                n *= s
            return jnp.zeros((n,), jnp.float32).at[p["idx"]].set(p["vals"]).reshape(p["shape"])

        return jax.tree.map(one, payload, is_leaf=lambda x: isinstance(x, dict) and "idx" in x)

    return Compressor(
        f"topk{ratio}",
        compress,
        decompress,
        _sparse_wire_bytes(ratio),
        compress_plane=_plane_compress_fn(lambda x2: _topk_rows(x2, ratio)[0]),
        fingerprint=("topk", float(ratio)),
    )


def randk_compressor(ratio: float = 0.01, seed: int = 0) -> Compressor:
    """Random-k sparsification with error feedback.

    The selection key rotates every call (otherwise the same coordinates
    are sent forever and the residual on the rest never drains). With
    error feedback the kept values are sent UNscaled — EF supplies the
    missing mass over rounds; 1/ratio rescaling would double-count.

    The rotating counter is host-side Python state, so randk has no plane
    twin and an empty fingerprint: the server falls back to the per-client
    loop and the grid engine marks its points opaque. The counter IS
    checkpointable, though — ``state_get``/``state_set`` expose it to the
    round-boundary protocol so killed randk runs resume bitwise.
    """
    counter = [0]  # call counter: rotates coordinate selection

    def compress(delta, residual):
        round_key = jax.random.PRNGKey(seed)
        round_key = jax.random.fold_in(round_key, counter[0])
        counter[0] += 1

        def one(path_hash, d, r):
            x = d.astype(jnp.float32) + (r.astype(jnp.float32) if r is not None else 0.0)
            flat = x.reshape(-1)
            k = max(int(flat.shape[0] * ratio), 1)
            key = jax.random.fold_in(round_key, path_hash)
            idx = jax.random.choice(key, flat.shape[0], (k,), replace=False)
            kept = flat[idx]
            sparse = jnp.zeros_like(flat).at[idx].set(kept)
            return {"idx": idx, "vals": kept, "shape": d.shape}, (flat - sparse).reshape(d.shape)

        if residual is None:
            residual = jax.tree.map(lambda d: jnp.zeros(d.shape, jnp.float32), delta)
        leaves_d, treedef = jax.tree.flatten(delta)
        leaves_r = treedef.flatten_up_to(residual)
        pairs = [one(i, d, r) for i, (d, r) in enumerate(zip(leaves_d, leaves_r))]
        payload = jax.tree.unflatten(treedef, [p[0] for p in pairs])
        new_res = jax.tree.unflatten(treedef, [p[1] for p in pairs])
        return payload, new_res

    def decompress(payload):
        def one(p):
            n = 1
            for s in p["shape"]:
                n *= s
            return jnp.zeros((n,), jnp.float32).at[p["idx"]].set(p["vals"]).reshape(p["shape"])

        return jax.tree.map(one, payload, is_leaf=lambda x: isinstance(x, dict) and "idx" in x)

    return Compressor(
        f"randk{ratio}",
        compress,
        decompress,
        _sparse_wire_bytes(ratio),
        state_get=lambda: {"counter": counter[0]},
        state_set=lambda s: counter.__setitem__(0, int(s["counter"])),
    )


def int8_compressor() -> Compressor:
    """Per-leaf symmetric int8 quantization with error feedback.

    Rounding is deterministic round-half-up, matching the Pallas row
    kernel bit for bit (the plane/sequential parity contract).
    """

    def compress(delta, residual):
        def one(d, r):
            x = d.astype(jnp.float32) + (
                r.astype(jnp.float32) if r is not None else 0.0
            )
            x2 = x.reshape(1, -1)
            deq2, q, scale = _int8_rows(x2)
            return (
                {"q": q[0].reshape(d.shape), "scale": scale[0]},
                (x2 - deq2).reshape(d.shape),
            )

        return _leafwise(delta, residual, one)

    def decompress(payload):
        return jax.tree.map(
            lambda p: p["q"].astype(jnp.float32) * p["scale"],
            payload,
            is_leaf=lambda x: isinstance(x, dict) and "q" in x,
        )

    def wire_bytes(t):
        return tree_size(t) + 4 * len(jax.tree.leaves(t))  # 1B/elem + scale

    return Compressor(
        "int8",
        compress,
        decompress,
        wire_bytes,
        compress_plane=_plane_compress_fn(lambda x2: _int8_rows(x2)[0]),
        fingerprint=("int8",),
    )


def bf16_compressor() -> Compressor:
    """bf16 truncation (2 B/element, no index overhead) with error feedback
    soaking up the dropped mantissa bits."""

    def compress(delta, residual):
        def one(d, r):
            x = d.astype(jnp.float32) + (
                r.astype(jnp.float32) if r is not None else 0.0
            )
            x2 = x.reshape(1, -1)
            deq2, b = _bf16_rows(x2)
            return {"bf16": b[0].reshape(d.shape)}, (x2 - deq2).reshape(d.shape)

        return _leafwise(delta, residual, one)

    def decompress(payload):
        return jax.tree.map(
            lambda p: p["bf16"].astype(jnp.float32),
            payload,
            is_leaf=lambda x: isinstance(x, dict) and "bf16" in x,
        )

    return Compressor(
        "bf16",
        compress,
        decompress,
        lambda t: 2 * tree_size(t),
        compress_plane=_plane_compress_fn(lambda x2: _bf16_rows(x2)[0]),
        fingerprint=("bf16",),
    )


def get_compressor(name: str, **kw) -> Compressor:
    if name == "none":
        return none_compressor()
    if name == "topk":
        return topk_compressor(kw.get("ratio", 0.01))
    if name == "randk":
        return randk_compressor(kw.get("ratio", 0.01), kw.get("seed", 0))
    if name == "int8":
        return int8_compressor()
    if name == "bf16":
        return bf16_compressor()
    raise ValueError(f"unknown compressor {name}")


def compressed_bytes(comp: Compressor, tree) -> int:
    return comp.wire_bytes(tree)
