"""Region cost model: FLOP / byte estimation over bound symbols.

The fusion layer used to make every decision greedily: any checker-approved
Pallas claim won, every claimed kernel split the surrounding XLA region, and
horizontal merges didn't exist. This module provides the small analytical
model those decisions now consult:

- ``bsym_cost(bsym)`` — (flops, bytes moved) for one bound symbol, recursing
  into composite decompositions. Matmul-class prims (``OpTags.MATMUL_OP``)
  count 2·M·N·K FLOPs; everything else is modeled as bandwidth-bound
  (bytes = inputs + outputs, flops = output elements).
- ``region_cost(bsyms)`` — cost of a fused region: FLOPs add up, but bytes
  count only the region *boundary* (inputs read + outputs written) — fusion's
  entire point is that interior values never touch HBM.
- ``arithmetic_intensity`` / ``is_memory_bound`` — position relative to the
  TPU ridge point (v5e ≈ 197 TFLOP/s bf16 over ~819 GB/s HBM ≈ 240
  FLOP/byte).
- ``horizontal_merge_profitable`` — the byte model for merging k sibling
  GEMMs over a shared input into one wide GEMM (the QKV pattern).
- ``claim_worthwhile`` — whether a standalone custom-kernel claim of a
  memory-bound op beats leaving it inside an XLA fusion region.

Estimates are deliberately coarse (no layout/padding modeling): they only
need to rank alternatives, not predict runtimes.
"""

from __future__ import annotations

from thunder_tpu.core.devices import CHIP_SPECS
from thunder_tpu.core.prims import OpTags, PrimIDs
from thunder_tpu.core.proxies import TensorProxy
from thunder_tpu.core.symbol import BoundSymbol
from thunder_tpu.core.utils import consumed_vars, produced_vars

# The model prices ONE chip: the v5e row of the device table
# (core/devices.py::CHIP_SPECS — peaks and budgets live there, with their
# source). CPU runs rehearse this same plan with interpret-mode kernels; on
# an accelerator the Pallas executor refuses to claim unless the attached
# chip's device_kind IS this row (executors/pallasex.py::_on_tpu), so these
# figures are never silently applied to a different device.
MODELED_DEVICE_KIND = "TPU v5 lite"
_CHIP = CHIP_SPECS[MODELED_DEVICE_KIND]

# peak matmul rate (bf16) — shared by the sub-block model below.
TPU_PEAK_FLOPS = _CHIP.peak_bf16_flops

# bf16 peak over HBM bandwidth; the ridge point of the roofline (~240).
TPU_RIDGE_FLOPS_PER_BYTE = _CHIP.peak_bf16_flops / _CHIP.hbm_bytes_per_s

# VMEM a single Pallas kernel invocation is PLANNED against: Mosaic's default
# scoped limit on this chip. Block-planner feasibility checks model against it.
VMEM_BUDGET_BYTES = _CHIP.scoped_vmem_default_bytes

# ...and what the planned megakernels are COMPILED with (vmem_limit_bytes).
# The staging models below leave out Mosaic's own scratch, layout padding
# and semaphores — the bench-geometry MLP sub-block models 15.7 MB and the
# compiler allocates 16.01 MiB — so the compiler's limit sits at twice the
# planner's budget: a quarter of this chip's physical VMEM. The one-pass
# flash backward is gated on its staging as the compiler allocates it
# (24.00 MiB at the training shape) directly against this limit
# (executors/pallasex.py::_sdpa_bwd_rung).
VMEM_LIMIT_BYTES = 2 * VMEM_BUDGET_BYTES
assert VMEM_LIMIT_BYTES <= _CHIP.vmem_bytes

# Below this many bytes of traffic a dedicated kernel launch can't amortize
# its dispatch + pipeline-fill overhead against XLA's fused code (~1 MiB is
# roughly 1.2 us of HBM time on v5e, the same order as kernel launch).
MIN_CLAIM_BYTES = 1 << 20

# --- per-platform calibration overlay --------------------------------------
# The hand-modeled constants below (efficiencies, launch overheads, ICI
# bandwidth) are v5e figures. observe/calibrate.py fits platform-specific
# values from the measured-time residual ledger (observe/profile.py) and
# installs them HERE as an overlay: every cost function reads its constants
# through ``constant(name)``, and every cost dict produced under an active
# overlay is stamped ``"calibration": <platform>`` — which the decision log
# turns into a typed ``calibrated[...]`` reason prefix. Verdicts never
# change silently.

CALIBRATABLE = (
    "ADAMW_LAUNCH_OVERHEAD_US", "ADAMW_HBM_GBPS", "ADAMW_CHAIN_EFFICIENCY",
    "ADAMW_FUSED_EFFICIENCY", "SUBBLOCK_XLA_EFFICIENCY",
    "SUBBLOCK_FUSED_EFFICIENCY", "SUBBLOCK_LAUNCH_OVERHEAD_US",
    "COLLECTIVE_LAUNCH_US", "ICI_BW_BYTES_PER_S",
)

_calibration_platform: str | None = None
_calibration: dict = {}


def constant(name: str) -> float:
    """Read a cost-model constant through the calibration overlay: the
    fitted per-platform value when one is installed, the hand-modeled
    module default otherwise."""
    return _calibration.get(name, globals()[name])


def apply_calibration(platform: str, constants: dict) -> None:
    """Install fitted constants for ``platform``. Unknown names are
    rejected loudly — a schema drift between the persisted calibration and
    ``CALIBRATABLE`` must fail, not silently half-apply."""
    global _calibration_platform, _calibration
    unknown = sorted(set(constants) - set(CALIBRATABLE))
    if unknown:
        raise ValueError(f"apply_calibration: unknown constant(s) {unknown}; "
                         f"calibratable: {list(CALIBRATABLE)}")
    _calibration = {k: float(v) for k, v in constants.items()}
    _calibration_platform = str(platform)


def clear_calibration() -> None:
    """Drop the overlay — back to the hand-modeled defaults."""
    global _calibration_platform, _calibration
    _calibration = {}
    _calibration_platform = None


def calibration_platform() -> str | None:
    """The platform whose fitted constants are installed, or ``None``."""
    return _calibration_platform


def stamp_calibration(cost: dict) -> dict:
    """Mark a cost dict as computed under the active overlay (no-op when
    uncalibrated). The decision log keys its typed ``calibrated[...]``
    reason prefix off this stamp."""
    if _calibration_platform is not None:
        cost["calibration"] = _calibration_platform
    return cost


_ZERO_COST_IDS = {
    PrimIDs.PYTHON_RETURN, PrimIDs.COMMENT, PrimIDs.PYTHON_DEL,
    PrimIDs.PYTHON_PRINT, PrimIDs.SINK, PrimIDs.UNPACK_TRIVIAL,
    PrimIDs.CHECK_TENSOR_SHAPE_AND_METADATA, PrimIDs.CHECK_NUMBER_TYPE_AND_VALUE,
    PrimIDs.CHECK_STRING_VALUE, PrimIDs.CHECK_LITERAL_LIKE, PrimIDs.CHECK_NUMBER_TYPE,
}


def tensor_bytes(p) -> int:
    """Bytes of one tensor proxy (0 for non-tensors)."""
    if not isinstance(p, TensorProxy):
        return 0
    n = 1
    for s in p.shape:
        n *= int(s)
    return n * p.dtype.bytes


def _io_bytes(bsym: BoundSymbol) -> int:
    return (sum(tensor_bytes(p) for p in bsym.flat_proxy_args())
            + sum(tensor_bytes(p) for p in bsym.flat_proxy_outs()))


def _matmul_flops(bsym: BoundSymbol) -> int:
    """2·(batch·M·N)·K for dot_general; conservative fallbacks for the other
    MATMUL_OP prims (einsum/convolution) via output-elements × contracted
    extent when recoverable, else output elements."""
    out_elems = 0
    for p in bsym.flat_proxy_outs():
        if isinstance(p, TensorProxy):
            n = 1
            for s in p.shape:
                n *= int(s)
            out_elems += n
    if bsym.sym.id is PrimIDs.DOT_GENERAL:
        a = bsym.args[0]
        contract_dims = bsym.kwargs.get("contract_dims")
        if contract_dims is None and len(bsym.args) > 2:
            contract_dims = bsym.args[2]
        k = 1
        if contract_dims and isinstance(a, TensorProxy):
            for d in contract_dims[0]:
                k *= int(a.shape[d])
        return 2 * out_elems * max(k, 1)
    if bsym.sym.id is PrimIDs.CONVOLUTION and isinstance(bsym.args[1], TensorProxy):
        w = bsym.args[1]
        k = 1
        for s in w.shape[1:]:  # Cin/groups × kernel window
            k *= int(s)
        return 2 * out_elems * max(k, 1)
    # einsum / convolution_backward: assume a square-ish contraction
    return 2 * out_elems * 128


def bsym_cost(bsym: BoundSymbol) -> tuple[int, int]:
    """(flops, bytes) of one bound symbol. Composites recurse into their
    decomposition (flops add; bytes are the composite's own boundary — the
    decomposition is assumed to fuse)."""
    if bsym.sym.id in _ZERO_COST_IDS:
        return 0, 0
    if OpTags.MATMUL_OP in bsym.sym.tags:
        return _matmul_flops(bsym), _io_bytes(bsym)
    if bsym.subsymbols:
        flops = sum(bsym_cost(s)[0] for s in bsym.subsymbols)
        return flops, _io_bytes(bsym)
    out_elems = sum(tensor_bytes(p) // max(p.dtype.bytes, 1)
                    for p in bsym.flat_proxy_outs() if isinstance(p, TensorProxy))
    return out_elems, _io_bytes(bsym)


def region_cost(bsyms) -> tuple[int, int]:
    """(flops, boundary bytes) of a fused region: interior traffic is free."""
    flops = sum(bsym_cost(b)[0] for b in bsyms)
    produced = set()
    counted = set()  # each boundary input is read once, however many members consume it
    in_bytes = 0
    for b in bsyms:
        for v in consumed_vars(b):
            if v not in produced and v not in counted:
                counted.add(v)
                in_bytes += tensor_bytes(v.proxy)
        produced |= produced_vars(b)
    # boundary outputs are unknowable without liveness; upper-bound with all
    # produced top-level outputs
    out_bytes = sum(tensor_bytes(p) for b in bsyms for p in b.flat_proxy_outs())
    return flops, in_bytes + out_bytes


def arithmetic_intensity(flops: int, nbytes: int) -> float:
    return flops / nbytes if nbytes else float("inf")


def is_memory_bound(flops: int, nbytes: int) -> bool:
    return arithmetic_intensity(flops, nbytes) < TPU_RIDGE_FLOPS_PER_BYTE


def claim_worthwhile(bsym: BoundSymbol) -> bool:
    """Should a standalone custom-kernel claim of this op beat leaving it to
    XLA fusion? Compute-bound ops (attention, big GEMM epilogues): always —
    the hand kernel wins on FLOP scheduling. Memory-bound ops: only when the
    working set is large enough to amortize a separate kernel launch."""
    flops, nbytes = bsym_cost(bsym)
    if not is_memory_bound(flops, nbytes):
        return True
    return nbytes >= MIN_CLAIM_BYTES


# --- fused multi-tensor optimizer model -----------------------------------
# The AdamW update is pure HBM-bound pointwise: read g,p,m,v + write p,m,v.
# The r5 breakdown (builder run, 2026-08) measured the per-parameter fused
# chains at ~45% of nominal HBM bandwidth at the bench scale (34 ms against a
# 14.7 ms roofline; a hand-written pure-jax layout measured the same, so the
# inefficiency is the per-fusion 7-stream access pattern, not framework
# overhead). The multi-tensor kernel walks full (rows, lanes) tiles of each
# stream — modeled at 85% — and replaces n fusions with one launch per
# aligned matrix plus one for the remainder.
ADAMW_LAUNCH_OVERHEAD_US = 8.0   # per-fusion dispatch + pipeline fill, v5e
ADAMW_HBM_GBPS = _CHIP.hbm_bytes_per_s / 1e9   # nominal HBM bandwidth
ADAMW_CHAIN_EFFICIENCY = 0.45    # measured: per-param fused pointwise chains
ADAMW_FUSED_EFFICIENCY = 0.85    # modeled: one contiguous slab per operand


def fused_adamw_cost(n_tensors: int, total_bytes: int,
                     slab_persistent: bool = False) -> dict:
    """Bytes-moved model for one optimizer dtype bucket: estimated µs for the
    per-parameter chains vs one flattened multi-tensor launch.
    ``total_bytes`` is the update's moved bytes (g,p,m,v reads + p,m,v
    writes, in their stored dtypes). Returned dict feeds the decision log
    (``observe.explain`` shows why each bucket did or didn't fuse).

    PACKING: no staging is charged to the fused path, and since PR 21 none
    exists for the bytes that matter — the Pallas claim updates every
    tile-aligned matrix of the bucket in place in its own layout (p/m/v
    alias their outputs) and packs only the unaligned remainder (norm
    vectors, biases) into a slab. The first implementation packed EVERY
    tensor on the assumption that XLA would absorb the concatenates; it
    cannot fuse them into a Mosaic custom call, and AOT-compiled for a v5e
    the packs were 5.0 GiB of temporaries for one 7B-geometry layer (the
    2-layer bench bucket did not fit 16 GB). ``pack_bytes_if_unabsorbed``
    stays in the dict as the UPPER bound — what a bucket of nothing but
    unaligned tensors would stage.

    ``slab_persistent=True`` (``optim.AdamW(slab_persistent=True)``): m/v
    live packed in per-dtype-bucket ``(rows, 128)`` slabs BETWEEN steps —
    the m/v pack/unpack around the kernel no longer exists (the kernel
    reads and writes the persistent slabs directly), so the
    ``pack_bytes_if_unabsorbed`` downside is zero BY CONSTRUCTION for the
    state streams (the p/g pack remains: on this path p and g are still
    staged in full every step). The dict says which layout the verdict was
    computed under."""
    launch = constant("ADAMW_LAUNCH_OVERHEAD_US")
    stream_us = total_bytes / (constant("ADAMW_HBM_GBPS") * 1e3)
    unfused = stream_us / constant("ADAMW_CHAIN_EFFICIENCY") + n_tensors * launch
    fused = stream_us / constant("ADAMW_FUSED_EFFICIENCY") + launch
    # the exposed staging traffic if XLA does NOT absorb the packs: one
    # read+write per staged stream, ~2x the update bytes when all 7 streams
    # (g,p,m,v in + p,m,v out) stage. Slab-persistent m/v never stage — the
    # downside term is ZERO by construction; the p/g packs that remain
    # exposed to XLA's concatenate fusion (~5/12 of the old figure: p+g is
    # half the reads, p a third of the writes) are surfaced separately as
    # ``pg_pack_bytes_if_unabsorbed`` so the residual risk stays visible
    # without re-inflating the term the layout removed.
    cost = {"tensors": n_tensors, "total_bytes": total_bytes,
            "saved_launches": max(n_tensors - 1, 0),
            "slab_persistent": bool(slab_persistent),
            "pack_bytes_if_unabsorbed": 0 if slab_persistent else 2 * total_bytes,
            "stream_us": round(stream_us, 3),
            "est_unfused_us": round(unfused, 3), "est_fused_us": round(fused, 3),
            "est_saved_us": round(unfused - fused, 3)}
    if slab_persistent:
        cost["pg_pack_bytes_if_unabsorbed"] = (2 * total_bytes) * 5 // 12
    return stamp_calibration(cost)


# --- collective overlap model ----------------------------------------------
# Ring-model transfer times and the in-flight buffer budget consumed by the
# overlap-scheduling pass (distributed/comm_reorder.py). The byte formulas
# are the SAME ring model observe.census applies to the optimized HLO, so a
# modeled overlap window and the census's recv-byte gauges agree on what a
# collective costs.
ICI_BW_BYTES_PER_S = 9e10        # v5p per-axis ICI bandwidth (benchmarks/northstar.py)
COLLECTIVE_LAUNCH_US = 5.0       # per-collective issue overhead (dispatch + ring setup)
COLLECTIVE_INFLIGHT_CAP_BYTES = 64 * 1024 * 1024  # outstanding-future buffer budget
COMM_BUCKET_MIN_BYTES = 1 << 20  # collectives below this coalesce (per member)
COMM_BUCKET_MAX_BYTES = 16 << 20  # one fused bucket never exceeds this payload

# peak FLOPs per µs and HBM bytes per µs, for per-op compute-time estimates
_FLOPS_PER_US = TPU_PEAK_FLOPS / 1e6
_HBM_BYTES_PER_US = ADAMW_HBM_GBPS * 1e3


def bsym_us(bsym: BoundSymbol) -> float:
    """Modeled execution time of one bound symbol in µs: the roofline max of
    its FLOP time (peak matmul rate) and its HBM time (nominal bandwidth).
    Coarse on purpose — the overlap scheduler only needs to rank how much
    compute fits inside a collective's transfer window."""
    flops, nbytes = bsym_cost(bsym)
    return max(flops / _FLOPS_PER_US, nbytes / _HBM_BYTES_PER_US)


# ring-model bytes received per device, keyed by the trace-level prim name
# (census.hlo_collectives applies the same formulas to HLO instruction kinds)
def ring_recv_bytes(kind: str, out_bytes: int, n_dev: int) -> int:
    if n_dev <= 1:
        return 0
    if kind in ("all_gather", "bucketed_all_gather", "synchronize", "regather"):
        return out_bytes * (n_dev - 1) // n_dev
    if kind in ("reduce_scatter", "bucketed_reduce_scatter"):
        return out_bytes * (n_dev - 1)
    if kind == "all_reduce":
        return 2 * out_bytes * (n_dev - 1) // n_dev
    if kind == "ppermute":
        return out_bytes
    return out_bytes * (n_dev - 1) // n_dev  # all_to_all and friends


def collective_transfer_us(kind: str, out_bytes: int, n_dev: int,
                           ici_bw: float | None = None) -> float:
    """Modeled ICI transfer time of one collective in µs (ring recv bytes
    over one axis's bandwidth) plus the fixed issue overhead. ``ici_bw``
    defaults to the (calibration-overlaid) ``ICI_BW_BYTES_PER_S``."""
    if ici_bw is None:
        ici_bw = constant("ICI_BW_BYTES_PER_S")
    recv = ring_recv_bytes(kind, out_bytes, n_dev)
    return constant("COLLECTIVE_LAUNCH_US") + recv / ici_bw * 1e6


def comm_bucket_cost(kind: str, member_bytes: list[int], n_dev: int,
                     ici_bw: float | None = None) -> dict:
    """Byte model for coalescing k sub-threshold collectives into one fused
    issue/wait pair: the ring transfer is linear in bytes, so fusing saves
    (k-1) issue overheads while moving the same payload. Returned dict feeds
    the bucket-verdict decision records (same ``est_*_us`` convention as
    ``fused_adamw_cost``)."""
    k = len(member_bytes)
    total = sum(member_bytes)
    unfused = sum(collective_transfer_us(kind, b, n_dev, ici_bw) for b in member_bytes)
    fused = collective_transfer_us(kind, total, n_dev, ici_bw)
    return stamp_calibration(
        {"members": k, "bucket_bytes": total,
         "recv_bytes": ring_recv_bytes(kind, total, n_dev), "n_dev": n_dev,
         "saved_issues": max(k - 1, 0),
         "est_unfused_us": round(unfused, 3), "est_fused_us": round(fused, 3),
         "est_saved_us": round(unfused - fused, 3)})


def fused_adamw_profitable(n_tensors: int, total_bytes: int) -> bool:
    """Fuse a bucket of n per-parameter AdamW chains into one multi-tensor
    launch? Singleton buckets never fuse (nothing to amortize); for the rest
    the estimate above decides — at bench scale both terms favor fusing
    (launches amortized AND slab streaming beats the 7-stream chains), tiny
    buckets fuse on the launch term alone. ``fused_optimizer=True/False``
    overrides per-compile."""
    if n_tensors < 2:
        return False
    c = fused_adamw_cost(n_tensors, total_bytes)
    return c["est_fused_us"] < c["est_unfused_us"]


# --- block-level (sub-block megakernel) model ------------------------------
# The block planner (core/fusion_passes.block_fusion_pass) rewrites a whole
# transformer MLP sub-block chain — residual add → rms_norm → gate/up GEMMs →
# act → mul → down GEMM → residual add — into ONE claimable composite
# (nn.mlp_subblock). Two questions gate every candidate, mirroring the
# fused_adamw modeled-vs-measured-efficiency structure:
#
# 1. VMEM residency: can the megakernel's per-grid-step staging (row tiles +
#    f32 scratch + double-buffered weight tiles) fit the scoped-VMEM budget?
#    Infeasible chains are never planned — a claim that compiles then dies on
#    chip would cost a quarantine round-trip for nothing.
# 2. Saved boundary bytes: the chain's interior values (normed activations,
#    gate/up pre-activations, the SwiGLU product, the down-projection) each
#    round-trip HBM once between XLA kernels in the unfused program; the
#    megakernel keeps them in VMEM. The byte saving must beat the fused
#    path's launch overhead and its (modeled) MXU-efficiency handicap vs
#    XLA's own GEMM scheduling.
#
# Only inference traces reach the planner (decode steps, prefill chunks): a
# train step's MLP GEMMs are XLA's, forward and backward (ledger, PR 29).
SUBBLOCK_XLA_EFFICIENCY = 0.84    # an estimate of XLA's forward and backward
                                  # GEMMs built on it read 14% high at 16,384
                                  # rows x 4096 x 14336: ~118 ms a layer where
                                  # the chip took ~103 (ledger, PR 29)
SUBBLOCK_FUSED_EFFICIENCY = 0.80  # modeled, and about right on the chip: the
                                  # forward kernel ran at 0.80-0.85 of what its
                                  # OWN structure allows (ledger, PR 26, at
                                  # 16,384 rows x 4096 x 14336: 60.4 ms where
                                  # its weights, streamed again for every row
                                  # block, need 55)
SUBBLOCK_LAUNCH_OVERHEAD_US = 8.0  # dispatch + pipeline fill (v5e, as adamw)
# kernel tile budgets — the SINGLE source of truth: executors/pallasex.py
# imports these for the megakernel's actual block picks, so the feasibility
# model above and the kernel's real staging can never drift apart
SUBBLOCK_ROW_BLOCK = 128
SUBBLOCK_FF_BLOCK = 128


def subblock_vmem_bytes(d_model: int, d_ff: int, dtype_bytes: int,
                        n_tokens: int | None = None) -> int:
    """Modeled per-grid-step VMEM staging of the sub-block megakernel:
    3 f32 row scratches (h, normed, accumulator) + 3 streamed row tiles
    (residual, x, out) + 3 double-buffered weight tiles (gate, up, down
    slices of ``SUBBLOCK_FF_BLOCK`` rows/cols)."""
    bn = min(SUBBLOCK_ROW_BLOCK, n_tokens) if n_tokens else SUBBLOCK_ROW_BLOCK
    bf = min(SUBBLOCK_FF_BLOCK, d_ff)
    return (3 * bn * d_model * 4            # h / normed / acc scratch (f32)
            + 3 * bn * d_model * dtype_bytes   # residual, x, out row tiles
            + 2 * 3 * bf * d_model * dtype_bytes)  # wg/wu/wd tiles, 2x buffered


def subblock_cost(n_tokens: int, d_model: int, d_ff: int,
                  dtype_bytes: int, decode: bool = False) -> dict:
    """Score one MLP sub-block chain for megakernel planning. Returns the
    decision-log dict: VMEM feasibility, the saved-boundary-bytes objective,
    and est_unfused/fused_us under the efficiency constants above.

    ``decode=True`` scores the chain as part of a T==1 serving decode step
    (the planner sets it when the chain's attention input comes from an
    ``nn.attn_subblock``): at one token per slot every GEMM of the unfused
    program is its own tiny-M kernel launch, so the unfused side is charged
    ``DECODE_UNFUSED_LAUNCHES_MLP`` launches — the launch amortization that
    makes decode-layer fusion win where the byte objective alone would lose
    at serving row counts. A forward-only chain at many rows (``decode=False``:
    the prefill ladder) keeps the pure byte objective, weights counted once."""
    flops = 3 * 2 * n_tokens * d_model * d_ff  # gate + up + down GEMMs
    # interior values written+read once each between kernels in the unfused
    # program: normed (N*D), gate pre-act (N*F), up (N*F), swiglu product
    # (N*F), down projection (N*D), plus the residual stream h (N*D) which
    # round-trips between the add and the norm
    interior_bytes = 2 * n_tokens * (3 * d_model + 3 * d_ff) * dtype_bytes
    # boundary traffic both variants pay: inputs (residual, x, weights) +
    # the block output
    boundary_bytes = (3 * n_tokens * d_model * dtype_bytes
                      + 3 * d_model * d_ff * dtype_bytes)
    flop_us = flops / TPU_PEAK_FLOPS * 1e6
    bw_us_per_byte = 1.0 / (constant("ADAMW_HBM_GBPS") * 1e3)
    launch = constant("SUBBLOCK_LAUNCH_OVERHEAD_US")
    unfused_launches = DECODE_UNFUSED_LAUNCHES_MLP if decode else 0
    unfused = (flop_us / constant("SUBBLOCK_XLA_EFFICIENCY")
               + (boundary_bytes + interior_bytes) * bw_us_per_byte
               + unfused_launches * launch)
    fused = (flop_us / constant("SUBBLOCK_FUSED_EFFICIENCY")
             + boundary_bytes * bw_us_per_byte + launch)
    vmem = subblock_vmem_bytes(d_model, d_ff, dtype_bytes, n_tokens)
    cost = {"n_tokens": n_tokens, "d_model": d_model, "d_ff": d_ff,
            "flops": flops, "decode": bool(decode),
            "saved_boundary_bytes": interior_bytes,
            "flop_us": round(flop_us, 3),
            "boundary_us": round(boundary_bytes * bw_us_per_byte, 3),
            "vmem_bytes_per_step": vmem,
            "vmem_feasible": vmem <= VMEM_BUDGET_BYTES,
            "est_unfused_us": round(unfused, 3), "est_fused_us": round(fused, 3),
            "est_saved_us": round(unfused - fused, 3)}
    return stamp_calibration(cost)


def subblock_profitable(cost: dict) -> bool:
    """Plan the chain? VMEM-infeasible never plans; otherwise the estimate
    must come out ahead: ``est_saved_us > 0``. Tiny traces lose on the 8 µs
    launch term alone. A decode chain wins on the launches it amortizes, a
    chain at prefill rows on its interior bytes.
    ``block_fusion=True/False`` overrides per-compile."""
    return bool(cost["vmem_feasible"]) and cost["est_saved_us"] > 0.0


# --- whole-decode-layer (serving T==1) model --------------------------------
# The decode-layer megakernel (core/fusion_passes attn sub-block walk +
# chaining stage) collapses one transformer layer of the serving decode step
# — rms_norm → qkv → rope → paged attention → out-proj → residual →
# MLP sub-block — into ONE Pallas launch per layer per decoded token. Two
# structural facts drive the model, both specific to T==1 decode:
#
# 1. The unfused program pays a kernel LAUNCH per GEMM: at one token per
#    slot every projection is a tiny-M matmul XLA cannot merge with its
#    neighbors, so the per-launch 8 µs dominates the per-launch compute.
# 2. The decomposition of nn.paged_decode_attention GATHERS each request's
#    whole block-table window into a contiguous (B, KV, L, hd) cache before
#    attending — per-token traffic the scalar-prefetch kernel never pays
#    (it DMAs pages straight off the block table and skips past-length
#    pages). Those gathered bytes are the dominant term of
#    ``saved_boundary_bytes`` at serving context lengths.
DECODE_UNFUSED_LAUNCHES_ATTN = 6   # q/k/v GEMMs + paged attention + out-proj
                                   # + the rope/scatter pointwise region
DECODE_UNFUSED_LAUNCHES_MLP = 4    # gate/up/down GEMMs + the pointwise glue
# What one step of the decode megakernel's grid costs with next to nothing
# in it — every operand's index map evaluated, every block's DMA decided —
# on top of its bytes and FLOPs. Measured (ledger, PR 25): the attention
# phase was S * KV * npg = 32,768 one-page steps a layer at 32 slots x 8 KV
# heads x 128 pages and held 161 ms of a 182 ms decode step = 0.61 us a
# step. The gate priced bytes, FLOPs and ONE launch, so the program that
# lost on the chip (below) won here; the fused side now pays for its steps.
DECODE_GRID_STEP_US = 0.6


# The decode attention walk (executors/pallasex.py::_walk_live_pages) stages
# a request's live K/V pages a BLOCK at a time: ``pages_per_block`` pages of
# each pool, double-buffered. A block is sized in bytes, not pages — tens of
# KB a head amortize the loop's fixed cost (descriptor issue, the semaphore
# waits, two small matmuls) without staging much past a short request's live
# rows — and shrinks when the kernel's other operands leave less VMEM than
# two pools x two buffers of it. A copy moves one page of ``heads`` KV heads
# (one descriptor, ``heads`` tiles): the walk's time follows the COUNT of
# copies until one carries 16-32 KB (ledger, PR 35; PERF.md PR 36).
DECODE_KV_BLOCK_BYTES = 128 * 1024


def decode_pages_per_block(page_size: int, head_dim: int, dtype_bytes: int,
                           pages_per_request: int,
                           vmem_left: int = VMEM_BUDGET_BYTES,
                           kv_heads: int = 1,
                           head_bytes: int = 0) -> tuple[int, int]:
    """``(pages, heads)`` of the decode walk's block: the pages of ONE pool
    and ONE head a buffer stages — from the page's bytes (``page_size x
    head_dim x dtype_bytes``), the block-table window and the VMEM the rest
    of the kernel leaves — and the KV heads a grid step walks, so one copy
    moves a page of: the largest divisor of ``kv_heads`` (the heads the
    caller's grid may group: the LOCAL heads under a tensor-parallel plan,
    1 where a head's step is tied to something else) whose staging, ``4 x
    heads x pages`` pages, with ``head_bytes`` more a head (the caller's
    query and output blocks, its softmax state), fits ``vmem_left``.
    Nothing a caller tunes."""
    page = page_size * head_dim * dtype_bytes
    ppb = max(1, min(DECODE_KV_BLOCK_BYTES // page, int(pages_per_request)))
    while ppb > 1 and 4 * ppb * page + head_bytes > vmem_left:
        ppb //= 2
    heads = max((d for d in range(1, kv_heads + 1) if kv_heads % d == 0
                 and d * (4 * ppb * page + head_bytes) <= vmem_left),
                default=1)
    return ppb, heads


def _decode_fixed_vmem_bytes(n_slots: int, d_model: int, n_heads: int,
                             kv_heads: int, head_dim: int, d_ff: int,
                             dtype_bytes: int) -> int:
    """The decode megakernel's staging WITHOUT the K/V blocks of the walk."""
    f32 = 4
    g = max(n_heads // max(kv_heads, 1), 1)
    resident = (n_slots * d_model * dtype_bytes            # h rows
                + 2 * n_slots * head_dim * dtype_bytes     # cos/sin (hd/2 x2)
                + n_slots * d_model * dtype_bytes          # normed rows
                + n_heads * n_slots * head_dim * dtype_bytes    # roped q
                + 2 * kv_heads * n_slots * head_dim * dtype_bytes  # fresh k/v
                + n_slots * g * head_dim * f32             # one head group's
                #                                            attention rows
                + n_slots * d_model * f32)                 # residual acc
    if d_ff:
        resident += 2 * n_slots * d_model * f32            # mlp norm + acc
    bf = min(SUBBLOCK_FF_BLOCK, d_ff) if d_ff else 0
    # every streamed operand owns its VMEM window for the WHOLE kernel —
    # Mosaic allocates per operand, not per phase — so the streamed tiles
    # SUM (each double-buffered), they don't max. This is what caps the
    # fully-chained decode layer at big-D geometries: the attention
    # sub-block alone fits where attn + the three MLP tiles together do
    # not, and the planner then keeps the two-launch form.
    tiles = (3 * head_dim * d_model                        # wq/wk/wv head tiles
             + d_model * g * head_dim                      # out-proj group tile
             + 3 * bf * d_model)                           # gate/up/down tiles
    return resident + 2 * tiles * dtype_bytes              # double-buffered


def decode_subblock_pages_per_block(n_slots: int, d_model: int, n_heads: int,
                                    kv_heads: int, head_dim: int,
                                    page_size: int, d_ff: int,
                                    dtype_bytes: int,
                                    pages_per_request: int) -> int:
    """``decode_pages_per_block`` for the decode megakernel: what its other
    operands leave of the planning budget bounds the block. One KV head a
    grid step: that head group's ``wo`` slice streams with it."""
    fixed = _decode_fixed_vmem_bytes(n_slots, d_model, n_heads, kv_heads,
                                     head_dim, d_ff, dtype_bytes)
    return decode_pages_per_block(page_size, head_dim, dtype_bytes,
                                  pages_per_request,
                                  vmem_left=VMEM_BUDGET_BYTES - fixed)[0]


def decode_subblock_vmem_bytes(n_slots: int, d_model: int, n_heads: int,
                               kv_heads: int, head_dim: int, page_size: int,
                               d_ff: int, dtype_bytes: int,
                               pages_per_request: int) -> int:
    """Modeled VMEM staging of the decode megakernel (``d_ff = 0`` models
    the attention sub-block alone): the whole slot batch's rows + rope
    tables + fresh K/V rows + one head group's attention rows stay resident;
    the f32 scratch holds the residual accumulator and (with the MLP
    chained) the second norm + down accumulator; the streamed tiles
    (per-head qkv weights, the per-group out-proj slice, the
    ``SUBBLOCK_FF_BLOCK`` MLP slices) are double-buffered, and so are the
    K and V blocks the page walk stages: ``decode_pages_per_block`` pages of
    each pool a buffer. The kernel in ``executors/pallasex.py`` takes its
    block from the same function, so this gate and the real staging cannot
    drift."""
    ppb = decode_subblock_pages_per_block(
        n_slots, d_model, n_heads, kv_heads, head_dim, page_size, d_ff,
        dtype_bytes, pages_per_request)
    return (_decode_fixed_vmem_bytes(n_slots, d_model, n_heads, kv_heads,
                                     head_dim, d_ff, dtype_bytes)
            # K and V, two buffers each
            + 4 * ppb * page_size * head_dim * dtype_bytes)


def attn_subblock_cost(n_slots: int, d_model: int, n_heads: int,
                       kv_heads: int, head_dim: int, page_size: int,
                       pages_per_request: int, dtype_bytes: int) -> dict:
    """Score one serving attention sub-block chain (T==1 decode). The
    decision-log dict mirrors ``subblock_cost``'s shape: VMEM feasibility,
    the saved-boundary-bytes objective (dominated by the decomposition's
    gathered contiguous cache), and est_unfused/fused_us with the unfused
    side charged ``DECODE_UNFUSED_LAUNCHES_ATTN`` kernel launches and the
    fused side its grid steps (``n_heads + 3 * kv_heads``: a head a step
    of q/k/v projection, a KV head a step of the page walk; none a page).

    Estimate against the chip, at 32 slots, D 4096, 32 / 8 heads x 128,
    16-token pages, window 2,048, 8 layers (my chip runs, PR 26, TPU v5
    lite; a whole decode step, same seed): fused 24.4 ms (this kernel 0.40
    ms a layer), planner off 57.3 ms, XLA only 75.3 ms — the estimate says
    0.50 ms fused against 1.16 ms unfused a layer: the right winner, the
    unfused side 6x too cheap (XLA's page gather runs at ~50 GB/s, not at
    the HBM rate charged here). With the one-page grid this kernel had
    until PR 26 the same three read 181.7 / 88.4 / 75.3 ms: the fused
    program was the slowest and the estimate, 0.46 ms, could not tell.
    Both sides are charged the WHOLE window's K/V bytes: the planner sees
    shapes, not lengths; the walk reads a request's live pages only."""
    L = pages_per_request * page_size              # block-table window
    qkv_w = (n_heads + 2 * kv_heads) * head_dim
    flops = (2 * n_slots * d_model * qkv_w                 # q/k/v projections
             + 2 * n_slots * n_heads * head_dim * L * 2    # scores + attn·V
             + 2 * n_slots * n_heads * head_dim * d_model)  # out-projection
    # interiors the unfused program round-trips between kernels: the normed
    # rows, the q/k/v projections (pre + post rope), the attention output
    # and the out-projection input — and, far larger, the decomposition's
    # gathered (B, KV, L, hd) contiguous K/V cache (write + read, x2 pools)
    gathered_bytes = 2 * 2 * n_slots * kv_heads * L * head_dim * dtype_bytes
    interior_bytes = (2 * n_slots * (2 * d_model + 2 * qkv_w
                                     + n_heads * head_dim) * dtype_bytes
                      + gathered_bytes)
    # boundary traffic both variants pay: the weights, the slot rows, and
    # the touched K/V pages
    boundary_bytes = ((qkv_w * d_model + d_model * n_heads * head_dim)
                      * dtype_bytes
                      + 2 * n_slots * d_model * dtype_bytes
                      + 2 * n_slots * kv_heads * L * head_dim * dtype_bytes)
    flop_us = flops / TPU_PEAK_FLOPS * 1e6
    bw_us_per_byte = 1.0 / (constant("ADAMW_HBM_GBPS") * 1e3)
    launch = constant("SUBBLOCK_LAUNCH_OVERHEAD_US")
    unfused = (flop_us / constant("SUBBLOCK_XLA_EFFICIENCY")
               + (boundary_bytes + interior_bytes) * bw_us_per_byte
               + DECODE_UNFUSED_LAUNCHES_ATTN * launch)
    grid_steps = n_heads + 3 * kv_heads
    fused = (flop_us / constant("SUBBLOCK_FUSED_EFFICIENCY")
             + boundary_bytes * bw_us_per_byte + launch
             + grid_steps * DECODE_GRID_STEP_US)
    vmem = decode_subblock_vmem_bytes(n_slots, d_model, n_heads, kv_heads,
                                      head_dim, page_size, 0, dtype_bytes,
                                      pages_per_request)
    return stamp_calibration(
        {"n_slots": n_slots, "d_model": d_model, "n_heads": n_heads,
         "kv_heads": kv_heads, "head_dim": head_dim,
         "context_window": L, "flops": flops, "grid_steps": grid_steps,
         "saved_boundary_bytes": interior_bytes,
         "flop_us": round(flop_us, 3),
         "boundary_us": round(boundary_bytes * bw_us_per_byte, 3),
         "vmem_bytes_per_step": vmem,
         "vmem_feasible": vmem <= VMEM_BUDGET_BYTES,
         "est_unfused_us": round(unfused, 3), "est_fused_us": round(fused, 3),
         "est_saved_us": round(unfused - fused, 3)})


def decode_layer_cost(attn_cost: dict, mlp_cost: dict, n_slots: int,
                      d_model: int, page_size: int, dtype_bytes: int) -> dict:
    """Score chaining a planned attention sub-block with its MLP sub-block
    into one ``nn.decode_layer`` launch. The chain adds two savings on top
    of the parts: one fewer kernel launch, and the residual stream h₂
    (the attention sub-block's output rows) never round-trips HBM between
    the two megakernels. VMEM feasibility is re-checked for the COMBINED
    staging — two individually-feasible halves can exceed the scoped
    budget together, in which case the planner keeps the two-launch form."""
    h2_roundtrip = 2 * n_slots * d_model * dtype_bytes
    bw_us_per_byte = 1.0 / (constant("ADAMW_HBM_GBPS") * 1e3)
    saved = (constant("SUBBLOCK_LAUNCH_OVERHEAD_US")
             + h2_roundtrip * bw_us_per_byte)
    vmem = decode_subblock_vmem_bytes(
        n_slots, d_model, attn_cost["n_heads"], attn_cost["kv_heads"],
        attn_cost["head_dim"], page_size, mlp_cost["d_ff"], dtype_bytes,
        attn_cost["context_window"] // page_size)
    return stamp_calibration(
        {"n_slots": n_slots, "d_model": d_model,
         "d_ff": mlp_cost["d_ff"], "context_window":
         attn_cost["context_window"],
         "saved_boundary_bytes": h2_roundtrip,
         "saved_launches": 1,
         "vmem_bytes_per_step": vmem,
         "vmem_feasible": vmem <= VMEM_BUDGET_BYTES,
         "est_saved_us": round(
             attn_cost["est_saved_us"] + mlp_cost["est_saved_us"] + saved,
             3)})


def horizontal_merge_profitable(m_tokens: int, out_features) -> bool:
    """Merge k sibling GEMMs (M×K)·(K×Nᵢ) into one (M×K)·(K×ΣNᵢ)?

    Split traffic:  k reads of the M×K activation + ΣNᵢ·K weights.
    Merged traffic: one M×K read + ΣNᵢ·K weights + a ΣNᵢ·K concat write
    (the merged weight is materialized per step — weights are trace inputs).

    Net win when (k-1)·M·K > ΣNᵢ·K, i.e. M·(k-1) > ΣNᵢ — the K and
    element-size terms cancel, so only the token count and output widths
    matter. Large-batch training merges (bench: M=16384, ΣNᵢ=12288 for 7B
    QKV), tiny traces don't (pass ``horizontal_fusion=True`` to force).
    """
    outs = list(out_features)
    if len(outs) < 2:
        return False
    return m_tokens * (len(outs) - 1) > sum(outs)
