"""Round-5 regression tests for the round-4 advisor findings.

1. identity_scale_op_clean_pass must not take the producer-rename
   branch when a control-flow sub-block reads the var by name (the
   global-block consumer scan alone under-counts readers).
2. attention_lstm_fuse_pass must not delete the parent-side atted
   precompute chain when a SECOND sub-block reads it.
3. (rewritten for the chip) a flash kernel that fails on a TPU backend
   raises; no probe or dispatcher falls back to the XLA reference.
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.ir import apply_pass


def _append(blk, t, ins, outs, attrs=None):
    blk.append_op(type=t, inputs=ins, outputs=outs, attrs=attrs or {})


def test_identity_scale_keeps_producer_read_by_sub_block():
    """Producer -> identity scale, where a sub-block ALSO reads the
    producer's output by name: the rename branch would leave the
    sub-block read dangling, so the pass must keep a writer of that
    name (advisor r4, ir.py identity_scale producer-rename guard)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        blk = main.global_block()
        x = fluid.layers.data("x", [4])
        mid = blk.create_var(name="mid_sub_read")
        _append(blk, "relu", {"X": [x]}, {"Out": [mid.name]})
        out = blk.create_var(name="ident_out")
        _append(blk, "scale", {"X": [mid]}, {"Out": [out.name]},
                {"scale": 1.0, "bias": 0.0})
        out2 = blk.create_var(name="post")
        _append(blk, "relu", {"X": [out]}, {"Out": [out2.name]})
        # a sub-block op reads mid_sub_read by name without the parent
        # op declaring it (recurrent/while body convention)
        sub = main._create_block(0)
        sread = sub.create_var(name="sub_out")
        _append(sub, "relu", {"X": [mid]}, {"Out": [sread.name]})
    apply_pass(main, "identity_scale_op_clean_pass")
    writers = [op for op in main.global_block().ops
               if "mid_sub_read" in op.output_arg_names]
    assert writers, ("sub-block read of mid_sub_read was starved: "
                     + str([o.type for o in main.global_block().ops]))
    # the identity scale itself may be removed via the rewire path, but
    # every remaining global read must resolve to a written var
    readers = [op for op in main.global_block().ops
               if "ident_out" in op.input_arg_names]
    if readers:
        assert any("ident_out" in op.output_arg_names
                   for op in main.global_block().ops)


def test_attention_lstm_fuse_skips_shared_atted():
    """A second control-flow sub-block reading the atted precompute var
    must veto the fuse (advisor r4, ir.py attention_lstm chain
    removal): removing the parent-side chain would starve it."""
    import paddle_tpu.fluid.nets as nets

    B, T, M, D = 3, 5, 6, 4
    main, startup = fluid.Program(), fluid.Program()
    exe = fluid.Executor()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [T, M], dtype="float32")
        hidden, cell = nets.attention_lstm(x, size=D)
    # atted = the global-block reshape2 output with no global consumer
    blk = main.global_block()
    g_reads = {n for op in blk.ops for n in op.input_arg_names}
    atted = [op.output("Out")[0] for op in blk.ops
             if op.type == "reshape2"
             and op.output("Out")[0] not in g_reads]
    assert len(atted) == 1, atted
    extra = main._create_block(0)
    ev = extra.create_var(name="extra_read_out")
    _append(extra, "relu", {"X": [atted[0]]}, {"Out": [ev.name]})
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        apply_pass(main, "attention_lstm_fuse_pass", scope=scope)
    types = [o.type for o in main.global_block().ops]
    assert "attention_lstm" not in types, types
    assert "recurrent" in types, types


def test_flash_failure_raises_instead_of_falling_back(monkeypatch):
    """There is no run-time probe and no swallow any more: on a TPU
    backend a flash kernel that fails raises out of `sdpa` /
    `sdpa_bshd` instead of silently handing back the XLA reference
    (which made a broken kernel look like a slow, working run). Only
    the shape gates and the PT_FLASH_ATTENTION=0 switch choose the
    reference."""
    import jax.numpy as jnp
    import pytest

    from paddle_tpu.ops import attention

    def broken_flash(*a, **k):
        raise RuntimeError("mosaic refused the kernel")

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention, "flash_attention", broken_flash)
    q = jnp.full((1, 2, 1024, 64), 0.5, jnp.float32)       # BHSD
    with pytest.raises(RuntimeError, match="mosaic refused"):
        attention.sdpa(q, q, q, is_causal=True)
    qb = jnp.swapaxes(q, 1, 2)                             # BSHD
    with pytest.raises(RuntimeError, match="mosaic refused"):
        attention.sdpa_bshd(qb, qb, qb, is_causal=True)
    # a shape gate is a choice, not failure handling: too short for
    # flash takes the reference without touching the kernel
    short = q[:, :, :128]
    assert attention.sdpa(short, short, short).shape == short.shape
    # and so is the documented off switch
    monkeypatch.setenv("PT_FLASH_ATTENTION", "0")
    assert attention._flash_usable() is False
    assert attention.sdpa(q, q, q, is_causal=True).shape == q.shape
