"""Registry-walk grad coverage guard (VERDICT r1 item 1).

Every prim that can appear on a float-tensor data path must either have a
VJP rule or be explicitly classified non-differentiable; every registered
composite must either have its own VJP rule, decompose into covered prims,
or be exempted here with a reason. A new op landing without grad coverage
fails this test instead of surfacing as a runtime NotImplementedError in a
user's training loop (the round-1 dropout failure mode).

Reference parity: breadth of ``thunder/core/transforms.py:599-1405``.
"""

import numpy as np
import pytest

import thunder_tpu as tt
import thunder_tpu.ops as ops
import thunder_tpu.ops.nn  # noqa: F401 — ensure nn composites are registered
from thunder_tpu.core import transforms as T
from thunder_tpu.core.prims import PrimIDs

# Utility prims that never carry float-tensor dataflow.
_UTILITY = {
    PrimIDs.PYTHON_RETURN, PrimIDs.PYTHON_DEL, PrimIDs.COMMENT, PrimIDs.PYTHON_PRINT,
    PrimIDs.SINK, PrimIDs.UNPACK_TRIVIAL, PrimIDs.CHECK_TENSOR_SHAPE_AND_METADATA,
    PrimIDs.CHECK_NUMBER_TYPE_AND_VALUE, PrimIDs.CHECK_STRING_VALUE,
    PrimIDs.CHECK_LITERAL_LIKE, PrimIDs.CHECK_NUMBER_TYPE, PrimIDs.ITEM,
}

# Prims that only ever appear inside an already-differentiated backward trace
# (second-order autodiff would need rules here; tracked, not silently zero —
# augmented_forward raises for them because they are not in _NONDIFF).
_SECOND_ORDER_TODO = {
    PrimIDs.CUMPROD_GRAD, PrimIDs.CUMPROD_TANGENT, PrimIDs.CONVOLUTION_BACKWARD,
}


def test_every_prim_classified_for_grad():
    missing = [
        p.name
        for p in PrimIDs
        if p not in T._vjp_rules
        and p not in T._NONDIFF
        and p not in _UTILITY
        and p not in _SECOND_ORDER_TODO
    ]
    assert not missing, (
        f"prims with neither a VJP rule nor a non-differentiable classification: {missing}. "
        "Register a rule in core/transforms.py or add to _NONDIFF/_UTILITY with a reason."
    )


def test_nondiff_rules_disjoint():
    overlap = [p for p in T._NONDIFF if p in T._vjp_rules]
    assert not overlap, f"prims both non-differentiable and ruled: {overlap}"


# Composites with a justified exemption from the OpInfo grad sweep.
# Every entry needs a reason; an empty-reason entry fails the test.
_COMPOSITE_GRAD_EXEMPT = {
    # integer/bool-valued outputs — nothing to differentiate
    "eq", "ne", "ge", "gt", "le", "lt", "logical_and", "logical_or", "logical_xor",
    "logical_not", "bitwise_and", "bitwise_or", "bitwise_xor", "bitwise_not",
    "bitwise_left_shift", "bitwise_right_shift", "sign", "signbit", "isnan", "isinf",
    "isfinite", "argmax", "argmin", "argsort", "floor", "ceil", "round", "trunc",
    "floor_divide", "nn.one_hot", "count_nonzero", "any", "all",
    # tensor-creation (no float-tensor inputs)
    "arange", "full", "zeros", "ones", "empty", "iota", "eye", "linspace",
    "zeros_like", "ones_like", "full_like", "rand_like", "randn_like",
    "bernoulli", "randn", "rand", "randint", "multinomial", "uniform",
    # random composites: differentiable wrt scale/shift only through decomposition
    "nn.dropout",  # pass-through + decomposition paths tested in this file
    # control/introspection
    "item", "shape", "numel", "detach", "stop_gradient", "device_put",
    "sharding_constraint",
}

# composite id -> reason it is exempt despite float-in/float-out
_COMPOSITE_GRAD_EXEMPT_REASONED = {
    "nn.ce_fwd": "internal fwd half of the CE fwd/bwd executor pair; the public "
                 "nn.cross_entropy composite has its own VJP rule",
    "nn.rms_norm_residual": "built POST-autodiff by the epilogue fusion pass "
                            "(core/fusion_passes.py) — autodiff never sees it; the "
                            "source ops (add + rms_norm) carry the grad story",
    "nn.linear_act": "built POST-autodiff by the epilogue fusion pass — autodiff "
                     "never sees it; linear and the activations carry the grad story",
    "nn.sdpa_fwd": "internal fwd half of SDPA; nn.scaled_dot_product_attention has a rule",
    "nn.paged_decode_attention": "inference-only serving decode path "
                                 "(thunder_tpu/serving/) — training traces use "
                                 "nn.scaled_dot_product_attention, which has a rule",
    "nn.banded_attention": "inference-only: a serving prefill chunk's attention "
                           "over keys gathered from the page pools "
                           "(models/cohere2_moe.py); training traces use "
                           "nn.scaled_dot_product_attention, which has a rule",
    "nn.moe_experts": "inference-only: the serving expert layer told which "
                      "experts it holds (no backward, no load-balancing term; "
                      "ROADMAP R2 lists training through it as left)",
    "nn.kda_chunk": "inference-only: a serving prefill chunk's delta rule from "
                    "a slot's recurrent state (models/solar_open2.py); no "
                    "VJP for the chunked scan yet (ROADMAP R5 (b))",
    "nn.kda_decode": "inference-only: one decode token of the delta rule for "
                     "every slot, the state updated in place "
                     "(models/solar_open2.py)",
    "nn.sdpa_bwd": "backward half; differentiating it is second-order autodiff",
    "ops.fmod": "prim classified non-differentiable (matches reference: grads stop)",
    "ops.remainder": "prim classified non-differentiable (matches reference)",
    "ops.copysign": "prim classified non-differentiable (matches reference)",
    "ops.nextafter": "prim classified non-differentiable (matches reference)",
    "ops.shift_left": "integer-only op",
    "ops.shift_right": "integer-only op",
    "ops.zeta": "d/dx has no closed form; d/dy rule registered, verified below",
    "ops.var_mean": "tuple output unsupported by the scalarizing grad harness; "
                    "grads covered via the var and mean OpInfos over the same prims",
    "ops.max_with_indices": "tuple (values, indices) output; values grad covered by amax",
    "ops.min_with_indices": "tuple (values, indices) output; values grad covered by amin",
    "ops.searchsorted": "integer-index output (insertion positions); non-differentiable",
    "ops.bucketize": "integer-index output; non-differentiable",
    "ops.bincount": "integer counting op (float only via weights, which scale "
                    "one-hot masks; grads stop at the integer input)",
    "ops.kthvalue": "tuple (values, indices) output; values grad covered by the "
                    "kthvalue_values OpInfo (gather-based decomposition)",
    "nn.grid_sample": "grads (input AND grid) verified vs torch autograd in "
                      "test_ops.py::test_grid_sample_grads_vs_torch",
    "nn.ctc_loss": "grads verified END-TO-END vs torch at the logits in "
                   "test_ops.py::test_ctc_loss_logits_grads (torch's own "
                   "log_probs-level grad folds the softmax Jacobian in, so a "
                   "per-op comparison is not meaningful)",
    "nn.ring_attention": "registered lazily by the context-parallel transform; its VJP "
                         "is the ring backward in distributed/ring.py, exercised by "
                         "tests/test_distributed.py ring-attention parity tests",
    "optim.adamw_step": "optimizer update chain — runs on detached grads/state "
                        "strictly after the backward; never differentiated",
    "optim.fused_adamw": "built POST-autodiff by the optimizer fusion pass "
                         "(core/fusion_passes.py) — autodiff never sees it; "
                         "never differentiated",
    "optim.fused_adamw_slab": "slab-persistent optimizer update — emitted by "
                              "AdamW(slab_persistent=True) on detached "
                              "grads/state strictly after the backward; "
                              "never differentiated",
    "nn.attn_subblock": "inference-only serving decode sub-block (built by the "
                        "block planner's attention walk on T==1 decode traces; "
                        "training attention goes through "
                        "nn.scaled_dot_product_attention, which has a rule)",
    "nn.decode_layer": "inference-only whole-decode-layer composite (the "
                       "chaining stage's unit) — serving decode traces are "
                       "never differentiated",
    "nn.mlp_subblock": "a serving composite (the block planner builds it on "
                       "inference traces only); no VJP rule: differentiates "
                       "through its decomposition, grads verified vs jax "
                       "autodiff in test_block_planner.py::"
                       "test_subblock_megakernel_backward_parity",
    "sentinel.observe_grads": "identity marker tagging grads for the numerics "
                              "guard — consumes DETACHED grads strictly after "
                              "the backward; stripped by the guard transform "
                              "or dropped by the claim pass, never "
                              "differentiated",
}

# OpInfo name -> composite ids its samples differentiate through (used when
# the OpInfo name doesn't literally match the composite id)
_OPINFO_COVERS = {
    "bce": ["nn.binary_cross_entropy"],
    "bce_with_logits": ["nn.binary_cross_entropy_with_logits"],
    "batch_norm_train": ["nn.batch_norm"],
}


def test_composite_grad_coverage_is_enumerable():
    """Every registered composite is (a) exercised by a differentiable OpInfo,
    (b) has its own VJP rule, or (c) is exempted above with a reason."""
    from opinfos import opinfos

    covered = set()
    for o in opinfos:
        if o.supports_grad:
            covered.add(o.name)
            covered.update(_OPINFO_COVERS.get(o.name, ()))
    reg = ops._opsym_registry
    unaccounted = []
    for op_id in sorted(reg):
        short = op_id.split(".")[-1]
        if op_id in T._vjp_rules:
            continue
        if op_id in _COMPOSITE_GRAD_EXEMPT or short in _COMPOSITE_GRAD_EXEMPT:
            continue
        if op_id in _COMPOSITE_GRAD_EXEMPT_REASONED:
            assert _COMPOSITE_GRAD_EXEMPT_REASONED[op_id], f"empty reason for {op_id}"
            continue
        if op_id in covered or short in covered:
            continue
        unaccounted.append(op_id)
    assert not unaccounted, (
        f"composites with no grad coverage story: {unaccounted}. Add a differentiable "
        "OpInfo, register a VJP rule, or exempt with a reason in this file."
    )


def test_zeta_second_arg_grad():
    """ADVICE r1: zeta grads were silently zero; now d/dy = -x * zeta(x+1, y)."""
    import jax
    from jax.scipy.special import zeta as jzeta
    import jax.numpy as jnp

    x = np.full((3,), 2.0, np.float32)
    q = np.array([1.5, 2.5, 3.5], np.float32)
    g = tt.jit(tt.grad(lambda a, b: ops.sum(ops.zeta(a, b)), argnums=1))(x, q)
    ref = jax.grad(lambda b: jzeta(jnp.asarray(x), b).sum())(jnp.asarray(q))
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref), rtol=1e-4)


def test_eval_mode_dropout_differentiates():
    """Round-1 regression: a pass-through composite (eval-mode dropout) on the
    grad path must not raise (ADVICE r1 high)."""
    import thunder_tpu.ops.nn as nn_ops

    a = np.random.RandomState(0).randn(4, 4).astype(np.float32)

    def f(x):
        return ops.sum(nn_ops.dropout(x, p=0.5, training=False))

    g = tt.jit(tt.grad(f))(a)
    np.testing.assert_allclose(np.asarray(g), np.ones_like(a))

    def f2(x):  # p=0 with training=True is also a pass-through
        return ops.sum(ops.mul(nn_ops.dropout(x, p=0.0, training=True), 2.0))

    g2 = tt.jit(tt.grad(f2))(a)
    np.testing.assert_allclose(np.asarray(g2), np.full_like(a, 2.0))


def test_training_dropout_grad_scales_kept_elements():
    """Training-mode dropout differentiates through its decomposition: grads
    are keep_mask / (1-p)."""
    import thunder_tpu.ops.nn as nn_ops

    a = np.random.RandomState(1).randn(64, 64).astype(np.float32)
    p = 0.25

    def f(x):
        return ops.sum(nn_ops.dropout(x, p=p, training=True))

    jf = tt.jit(lambda x: (f(x), tt.grad(f)(x)))
    # grad values must be exactly 0 or 1/(1-p)
    _, g = jf(a)
    g = np.asarray(g)
    scale = 1.0 / (1.0 - p)
    assert np.all(np.isclose(g, 0.0) | np.isclose(g, scale))
    frac_kept = np.mean(np.isclose(g, scale))
    assert 0.6 < frac_kept < 0.9  # ~0.75 expected
