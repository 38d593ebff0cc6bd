"""Every op of a compiled step says which layer it belongs to and which
part of it, and the compiled-step ledger keeps one row an instruction.

`monitor/scopes.py` is the one place that spells and parses the two kinds
of scope; `monitor/xla.py::parse_hlo_ops` the one parser of a compiled
module's text. What must be parsed as the chip's compiler prints it is in
`tests/test_tpu_lowering.py` (the v5e compile-only client lives in that
one file); here the grammar is held on hand-written text in the chip's
spelling and on programs compiled for the CPU."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import scopes
from deeplearning4j_tpu.monitor import xla as xla_ledger

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "deeplearning4j_tpu")


@pytest.fixture(autouse=True)
def _fresh_ledger():
    monitor.REGISTRY.reset()
    xla_ledger.disable_ledger()
    xla_ledger.clear_ledger()
    yield
    xla_ledger.disable_ledger()
    xla_ledger.clear_ledger()
    monitor.REGISTRY.reset()


@pytest.fixture(autouse=True, scope="module")
def _compiled_here():
    """JAX's persistent compile cache keys a program WITHOUT its op
    metadata, so an executable it hands back carries the op_names of the
    tree that compiled it: these tests read op_names, and compile."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


# ------------------------------------------------- the convention, parsed
@pytest.mark.parametrize("op_name,layer,part", [
    # forward, the forward made again, the backward: all name the layer
    ("jit(kstep)/while/body/closed_call/jvp()/layer:blk0/mlp/gated/dot_general",
     "blk0", "mlp/gated"),
    ("jit(kstep)/while/body/closed_call/transpose(jvp())/layer:blk0/"
     "checkpoint/rematted_computation/mlp/gated/dot_general",
     "blk0", "mlp/gated"),
    ("jit(kstep)/transpose(jvp(layer:3))/kda/scan/mul", "3", "kda/scan"),
    # a vertex's scope stands outside its layer scope; the inner part wins
    ("jit(k)/mtp/layer:mtp_block/layer:mtp_block/checkpoint/mla/attn/"
     "flash_fwd", "mtp_block", "mla/attn"),
    ("jit(k)/mtp/layer:mtp_ids/slice", "mtp_ids", "mtp"),
    # the innermost part behind the layer: a layer's own scan stands
    # under one part, the work inside it under others
    ("jit(k)/layer:2/moe/blocks/while/body/closed_call/moe/experts/mul",
     "2", "moe/experts"),
    ("jit(k)/layer:2/moe/blocks/while/body/closed_call/add_any", "2",
     "moe/blocks"),
    ("jit(k)/layer:head/cast/convert_element_type", "head", "cast"),
    # outside every layer
    ("jit(kstep)/while/body/closed_call/opt/update/add", None, "opt/update"),
    ("jit(kstep)/while/body/dynamic_slice", None, None),
    ("jit(k)/layer:blk0/layer:blk0/remat2", "blk0", None),
    ("", None, None),
])
def test_an_op_name_gives_its_layer_and_its_part(op_name, layer, part):
    assert scopes.parse(op_name) == (layer, part)


def test_a_layer_scope_is_told_by_its_prefix_alone():
    def f(x):
        with scopes.layer_scope("a/b(c)"):
            with jax.named_scope("dense"):
                return x * 2
    text = jax.jit(f).lower(jnp.ones(3)).as_text(debug_info=True)
    assert scopes.LAYER + "a_b_c_/dense" in text
    # no part spells a layer, and no part is listed twice
    names = [name for name, _ in scopes.PART_SCOPES]
    assert len(names) == len(set(names))
    assert not any(n.startswith(scopes.LAYER) for n in names)


def _literals():
    found = set()
    for sub in ("nn", "ops", "models"):
        for base, _, files in os.walk(os.path.join(PKG, sub)):
            for f in files:
                if f.endswith(".py"):
                    with open(os.path.join(base, f)) as fh:
                        src = fh.read()
                    found |= set(re.findall(
                        r'named_scope\(\s*"([^"]+)"\s*\)', src))
                    if sub == "models":     # add_layer(..., scope="mtp")
                        found |= set(re.findall(r'scope="([^"]+)"', src))
    return found


def test_the_tuple_is_what_the_code_enters_and_what_the_docs_list():
    names = {name for name, _ in scopes.PART_SCOPES}
    assert _literals() == names
    with open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    section = doc.split("### Scopes inside the compiled step", 1)[1]
    section = section.split("\n### ", 1)[0]
    rows = set(re.findall(r"^\| `([^`]+)` \|", section, re.M))
    assert rows == names | {scopes.LAYER + "<name>"}


def test_no_new_scope_spells_a_marker_an_older_reader_matches():
    """The benchmark's readers match these by SUBSTRING; a new part, alone
    or behind a layer of the zoo's models, must not spell one."""
    old = ("kda/proj", "kda/scan", "kda/out", "mla/proj", "mla/attn",
           "mla/rope", "moe/route", "moe/dispatch", "moe/experts",
           "moe/shared", "moe/combine", "mlp/gated", "head/loss",
           "opt/update", "mtp", "sconv/proj", "sconv/mix", "mha/proj",
           "mha/norm", "mha/rope", "mha/attn")
    new = [n for n, _ in scopes.PART_SCOPES if n not in old]
    layers = ["embed", "norm", "head"] + [f"layer{i}" for i in range(6)] \
        + [str(i) for i in range(9)]
    for part in new:
        for layer in layers:
            path = f"{scopes.LAYER}{layer}/{part}/add"
            assert not any(m in path for m in old), path


# -------------------------------------- the parser, on the chip's spelling
_TPU_TEXT = """\
HloModule jit_kstep, is_scheduled=true

%fused_computation.7 (p: bf16[128,256]) -> bf16[128,128] {
  %p = bf16[128,256]{1,0:T(8,128)(2,1)} parameter(0)
  %w = bf16[256,128]{1,0:T(8,128)(2,1)} constant({...})
  ROOT %convolution.3 = bf16[128,128]{1,0:T(8,128)(2,1)} convolution(%p, %w), dim_labels=bf_io->bf
}

%fused_computation.6 (param_0.11: bf16[128,256], param_1.15: bf16[4,8192,2048]) -> bf16[128,128] {
  %param_0.11 = bf16[128,256]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %param_1.15 = bf16[4,8192,2048]{2,1,0:T(8,128)(2,1)} parameter(1)
  %fusion.7 = bf16[128,128]{1,0:T(8,128)(2,1)} fusion(%param_0.11), kind=kLoop, calls=%fused_computation.7
  %k = bf16[128,64]{1,0:T(8,128)(2,1)} constant({...})
  %convolution.11 = bf16[128,64]{1,0:T(8,128)(2,1)} convolution(%fusion.7, %k), dim_labels=bf_io->bf, metadata={op_name="jit(kstep)/layer:blk0/mlp/gated/dot_general"}
  ROOT %convert.2 = bf16[128,128]{1,0:T(8,128)(2,1)} convert(%fusion.7)
}

%region_2.4 (arg.0: (bf16[128,256])) -> (bf16[128,256]) {
  %arg.0 = (bf16[128,256]{1,0:T(8,128)(2,1)}) parameter(0)
  %gte.1 = bf16[128,256]{1,0:T(8,128)(2,1)} get-tuple-element(%arg.0), index=0
  %copy.3 = bf16[128,256]{1,0:T(8,128)(2,1)} copy(%gte.1), metadata={op_name="jit(kstep)/layer:blk0/cond/branch_0_fun/moe/dispatch/gather"}
  ROOT %tuple.2 = (bf16[128,256]{1,0:T(8,128)(2,1)}) tuple(%copy.3)
}

%region_3.5 (arg.1: (bf16[128,256])) -> (bf16[128,256]) {
  %arg.1 = (bf16[128,256]{1,0:T(8,128)(2,1)}) parameter(0)
  %gte.2 = bf16[128,256]{1,0:T(8,128)(2,1)} get-tuple-element(%arg.1), index=0
  %ragged-dot-none.4 = bf16[128,256]{1,0:T(8,128)(2,1)} custom-call(%gte.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(kstep)/layer:blk0/cond/branch_1_fun/jit(_walk)/ragged-dot-none"}, backend_config={"custom_call_config":{"body":"convolution(%a, %b), dim_labels=bf_io->bf"}}
  ROOT %tuple.3 = (bf16[128,256]{1,0:T(8,128)(2,1)}) tuple(%ragged-dot-none.4)
}

%sum.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[]{:T(128)} parameter(0)
  %b = f32[]{:T(128)} parameter(1)
  ROOT %add.9 = f32[]{:T(128)} add(%a, %b), metadata={op_name="jit(kstep)/reduce_sum"}
}

%body.1 (t: (s32[], bf16[128,256], /*index=2*/bf16[4,8192,2048])) -> (s32[], bf16[128,256], /*index=2*/bf16[4,8192,2048]) {
  %t = (s32[]{:T(128)}, bf16[128,256]{1,0:T(8,128)(2,1)S(1)}, /*index=2*/bf16[4,8192,2048]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%t), index=0
  %x = bf16[128,256]{1,0:T(8,128)(2,1)S(1)} get-tuple-element(%t), index=1
  %stack = bf16[4,8192,2048]{2,1,0:T(8,128)(2,1)} get-tuple-element(%t), index=2
  %fusion.45 = bf16[128,128]{1,0:T(8,128)(2,1)} fusion(%x, %stack), kind=kOutput, calls=%fused_computation.6, metadata={op_name="jit(kstep)/while/body/closed_call/transpose(jvp())/layer:blk0/checkpoint/rematted_computation/mlp/gated/dot_general" stack_frame_id=5}, backend_config={"window_config":{"kernel_window_bounds":["32","2"]}}
  %fusion.46 = bf16[128,128]{1,0:T(8,128)(2,1)} fusion(%x, %stack), kind=kCustom, calls=%fused_computation.6, backend_config={"flag_configs":[]}
  %pred.1 = s32[]{:T(128)} constant(1)
  %arg.t = (bf16[128,256]{1,0:T(8,128)(2,1)}) tuple(%x)
  %cond.6 = (bf16[128,256]{1,0:T(8,128)(2,1)}) conditional(%pred.1, %arg.t, %arg.t), branch_computations={%region_2.4, %region_3.5}, metadata={op_name="jit(kstep)/layer:blk0/cond"}
  %copy.9 = bf16[128,256]{0,1:T(8,128)(2,1)} copy(%x), backend_config={"flag_configs":[]}
  %reduce.8 = f32[]{:T(128)} reduce(%copy.9, %pred.1), dimensions={0,1}, to_apply=%sum.1, metadata={op_name="jit(kstep)/layer:blk0/norm/reduce_sum"}
  ROOT %tuple.51 = (s32[]{:T(128)}, bf16[128,256]{1,0:T(8,128)(2,1)S(1)}, /*index=2*/bf16[4,8192,2048]{2,1,0:T(8,128)(2,1)}) tuple(%i, %x, %stack)
}

%cond.1 (t.1: (s32[], bf16[128,256], /*index=2*/bf16[4,8192,2048])) -> pred[] {
  %t.1 = (s32[]{:T(128)}, bf16[128,256]{1,0:T(8,128)(2,1)S(1)}, /*index=2*/bf16[4,8192,2048]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %i.1 = s32[]{:T(128)} get-tuple-element(%t.1), index=0
  %two = s32[]{:T(128)} constant(2)
  ROOT %lt.7 = pred[]{:T(512)} compare(%i.1, %two), direction=LT, metadata={op_name="jit(kstep)/while/cond/lt"}
}

ENTRY %main.11 (x.1: bf16[128,256], s.1: bf16[4,8192,2048]) -> bf16[128,256] {
  %x.1 = bf16[128,256]{1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="x"}
  %s.1 = bf16[4,8192,2048]{2,1,0:T(8,128)(2,1)} parameter(1)
  %zero = s32[]{:T(128)} constant(0)
  %copy-start.3 = (bf16[128,256]{1,0:T(8,128)(2,1)S(1)}, bf16[128,256]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%x.1)
  %copy-done.3 = bf16[128,256]{1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.3)
  %tuple.63 = (s32[]{:T(128)}, bf16[128,256]{1,0:T(8,128)(2,1)S(1)}, /*index=2*/bf16[4,8192,2048]{2,1,0:T(8,128)(2,1)}) tuple(%zero, %copy-done.3, %s.1)
  %while.7 = (s32[]{:T(128)}, bf16[128,256]{1,0:T(8,128)(2,1)S(1)}, /*index=2*/bf16[4,8192,2048]{2,1,0:T(8,128)(2,1)}) while(%tuple.63), condition=%cond.1, body=%body.1, metadata={op_name="jit(kstep)/while"}
  ROOT %out = bf16[128,256]{1,0:T(8,128)(2,1)} get-tuple-element(%while.7), index=1
}
"""


def test_the_parser_reads_the_chips_spelling():
    rows = {r["name"]: r for r in xla_ledger.parse_hlo_ops(_TPU_TEXT)}
    # parameters, tuples and their elements, constants: not rows; a
    # fusion's body and a reduce's computation belong to their instruction
    assert set(rows) == {"copy-start.3", "copy-done.3", "while.7",
                         "fusion.45", "fusion.46", "cond.6", "reduce.8",
                         "copy.3", "copy.9", "ragged-dot-none.4", "lt.7"}
    # the entry has no parent; a scan's body and condition name the while,
    # a branch names its conditional, which is a container by OPCODE
    assert rows["while.7"]["parent"] is None
    assert rows["fusion.45"]["parent"] == "while.7" == rows["lt.7"]["parent"]
    assert rows["cond.6"]["opcode"] == "conditional"
    assert rows["cond.6"]["opcode"] in xla_ledger.CONTAINER_OPCODES
    assert rows["copy.3"]["parent"] == "cond.6"
    assert rows["ragged-dot-none.4"]["parent"] == "cond.6"
    assert rows["copy.3"]["computation"] == "region_2.4"
    # a fusion sums its body, a fusion in the body too:
    # (128x256)@(256x128) and (128x128)@(128x64)
    fused = rows["fusion.45"]
    assert fused["dot_flops"] == 2 * 128 * 128 * 256 + 2 * 128 * 64 * 128
    assert fused["kind"] == "kOutput" and fused["opcode"] == "fusion"
    assert (fused["layer"], fused["part"]) == ("blk0", "mlp/gated")
    assert fused["recomputed"] and fused["direction"] == "backward"
    # a fusion XLA left without an op_name is placed by its body's, and
    # the instruction -> op_name view does not make one up for it
    bare = rows["fusion.46"]
    assert bare["scope"] is None and bare["kind"] == "kCustom"
    assert (bare["layer"], bare["part"]) == ("blk0", "mlp/gated")
    assert bare["dot_flops"] == fused["dot_flops"]
    assert not bare["recomputed"] and bare["direction"] == "forward"
    # a layout copy XLA made without an op_name is placed by the op it
    # serves; the prefetch that feeds only the loop's tuple by nothing
    served = rows["copy.9"]
    assert served["scope"] is None and served["opcode"] == "copy"
    assert (served["layer"], served["part"]) == ("blk0", "norm")
    assert (rows["copy-done.3"]["layer"], rows["copy-done.3"]["part"]) \
        == (None, None)
    # bytes as the shapes say: the stacked operand whole (an upper bound)
    assert fused["bytes_out"] == 128 * 128 * 2
    assert fused["bytes_in"] == 128 * 256 * 2 + 4 * 8192 * 2048 * 2
    # a custom call holds no dot, whatever its body's text says
    ragged = rows["ragged-dot-none.4"]
    assert ragged["dot_flops"] == 0 and ragged["opcode"] == "custom-call"
    assert (ragged["layer"], ragged["part"]) == ("blk0", None)
    assert not rows["copy.3"]["recomputed"]
    assert rows["copy.3"]["direction"] == "forward"
    assert rows["copy-start.3"]["scope"] is None
    assert rows["copy-start.3"]["direction"] is None
    assert rows["copy-start.3"]["bytes_out"] == 2 * 128 * 256 * 2 + 4
    # the views: instruction -> op_name for those that carry one
    table = xla_ledger.OpTable(rows.values())
    assert table["reduce.8"].endswith("layer:blk0/norm/reduce_sum")
    assert "copy-done.3" not in table and "fusion.46" not in table
    assert "copy.9" not in table and len(table.rows) == 11


@pytest.mark.parametrize("attrs,result,lhs,rhs,macs", [
    # a plain product as the TPU writes it
    ("dim_labels=bf_io->bf", [128, 64], [128, 256], [256, 64],
     128 * 64 * 256),
    # ResNet's 3x3 with padding: the taps on the padding are not products
    ("window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f",
     [2, 4, 4, 8], [2, 4, 4, 16], [3, 3, 16, 8],
     2 * 8 * 16 * (4 * 3 - 2) ** 2),
    # grouped features: the kernel holds I / groups input features
    ("window={size=3x3}, dim_labels=b01f_01io->b01f, feature_group_count=4",
     [2, 6, 6, 64], [2, 8, 8, 32], [3, 3, 8, 64], 2 * 6 * 6 * 64 * 9 * 8),
    # a batched product as a dilated window: eight products, not 64
    ("window={size=8 stride=7 lhs_dilate=8}, dim_labels=0bf_0io->0bf",
     [8, 128, 64], [8, 128, 32], [8, 32, 64], 8 * 128 * 64 * 32),
    # strided 1x1
    ("window={size=1x1 stride=2x2}, dim_labels=b01f_01io->b01f",
     [2, 4, 4, 8], [2, 8, 8, 16], [1, 1, 16, 8], 2 * 4 * 4 * 8 * 16),
])
def test_a_convolutions_flops_are_its_products(attrs, result, lhs, rhs,
                                               macs):
    assert xla_ledger._conv_flops(attrs, result, lhs, rhs) == 2 * macs


# ------------------------------------------ programs compiled for the CPU
def _rows(fn, *args):
    return xla_ledger.compiled_ops(jax.jit(fn).lower(*args).compile())


def test_a_dot_and_a_grouped_convolution_exact_against_the_shapes():
    a, b = jnp.ones((24, 40)), jnp.ones((40, 56))
    rows = _rows(lambda a, b: a @ b, a, b)
    assert sum(r["dot_flops"] for r in rows) == 2 * 24 * 56 * 40
    x, k = jnp.ones((2, 9, 9, 8)), jnp.ones((3, 3, 2, 12))

    def conv(x, k):
        return jax.lax.conv_general_dilated(
            x, k, (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=4)

    rows = _rows(conv, x, k)
    # result elements x kernel elements / output features
    assert sum(r["dot_flops"] for r in rows) \
        == 2 * (2 * 7 * 7 * 12) * (3 * 3 * 2 * 12) // 12


def test_scan_bodies_and_cond_branches_name_their_parent():
    def f(ws, x, flag):
        def body(c, w):
            with scopes.layer_scope("blk"), jax.named_scope("dense"):
                return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        with scopes.layer_scope("top"), jax.named_scope("merge"):
            return jax.lax.cond(flag, lambda a: a @ a.T,
                                lambda a: (a * 2) @ a.T, y)

    rows = _rows(f, jnp.ones((3, 16, 16)), jnp.ones((8, 16)), True)
    by = {r["name"]: r for r in rows}
    whiles = [r for r in rows if r["opcode"] == "while"]
    conds = [r for r in rows if r["opcode"] == "conditional"]
    assert len(whiles) == 1 and len(conds) == 1
    assert whiles[0]["parent"] is None and conds[0]["parent"] is None
    in_scan = [r for r in rows if r["parent"] == whiles[0]["name"]]
    assert any(r["dot_flops"] == 2 * 8 * 16 * 16 and r["layer"] == "blk"
               and r["part"] == "dense" for r in in_scan)
    in_cond = [r for r in rows if r["parent"] == conds[0]["name"]]
    assert {r["computation"] for r in in_cond} >= {
        r["computation"] for r in in_cond if r["dot_flops"]}
    assert sum(1 for r in in_cond if r["dot_flops"] == 2 * 8 * 8 * 16) == 2
    assert all(r["layer"] == "top" and r["part"] == "merge"
               for r in in_cond if r["dot_flops"])
    assert all(by[r["parent"]]["opcode"] in xla_ledger.CONTAINER_OPCODES
               for r in rows if r["parent"])


def test_a_checkpointed_layer_says_what_it_makes_again_and_which_way():
    """Inside a scan, as in the containers' steps (outside one, XLA folds
    the second forward into the first)."""
    from deeplearning4j_tpu.nn.layers.attention import GatedMLP
    from deeplearning4j_tpu.nn.conf.base import InputType
    from deeplearning4j_tpu.nn.multilayer import _layer_call
    layer = GatedMLP(n_out=16, hidden=32)
    params, _ = layer.init(jax.random.PRNGKey(0),
                           InputType.recurrent(16, 8), jnp.float32)
    stacked = jax.tree_util.tree_map(lambda a: jnp.stack([a, a, a]), params)
    cast = lambda p: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), p)

    def loss(stacked, x):
        def body(x, params):
            y, _ = _layer_call(layer, name="blk0", seq=False, train=True,
                               remat=True, params=params, x=x, state={},
                               cast=cast)
            return y, None
        y, _ = jax.lax.scan(body, x, stacked)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    rows = _rows(jax.grad(loss), stacked,
                 jnp.ones((2, 8, 16), jnp.bfloat16))
    dots = [r for r in rows if r["dot_flops"]]
    assert dots and all(r["layer"] == "blk0" and r["part"] == "mlp/gated"
                        for r in dots)
    # the forward's three products once in the forward scan and again,
    # marked, in the backward scan beside the backward's own
    first = [r for r in dots if r["direction"] == "forward"]
    again = [r for r in dots if r["recomputed"]]
    back = [r for r in dots if r["direction"] == "backward"
            and not r["recomputed"]]
    assert len(first) == 3 and len(again) >= 2 and len(back) >= 4
    assert all(r["direction"] == "backward" for r in again)
    assert len({r["parent"] for r in first}) == 1
    assert {r["parent"] for r in again} == {r["parent"] for r in back}
    assert {r["parent"] for r in again} != {r["parent"] for r in first}


# ------------------------------ three tiny nets: every op has a home
V, T, F = 64, 16, 32


def _graph_lm():
    """One latent-attention block, one short-convolution block with an
    expert layer, a head tied to the embedding, and a second loss on a
    branch with a scope of its own."""
    from deeplearning4j_tpu.nn.conf.base import InputType
    from deeplearning4j_tpu.nn.conf.graph_vertices import (
        MergeVertex, ShiftTimeSeriesVertex,
    )
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingSequenceLayer, RMSNormLayer, RnnOutputLayer,
        TransformerBlock,
    )
    from deeplearning4j_tpu.nn.layers.attention import (
        GatedMLP, LinearProjection, MoEFeedForward,
    )
    from deeplearning4j_tpu.nn.layers.linear_attention import (
        GatedShortConv, MultiHeadLatentAttention,
    )
    from deeplearning4j_tpu.nn.updaters import AdamW
    g = (NeuralNetConfiguration.Builder().seed(1)
         .updater(AdamW(1e-3, weight_decay=0.1, decay_matrices_only=True))
         .gradient_checkpointing(True).compute_dtype("bfloat16")
         .graph_builder().add_inputs("ids")
         .set_input_types(InputType.recurrent(1, T)))
    block = lambda attn, ffn: TransformerBlock(
        n_out=F, n_heads=2, norm="rms", has_bias=False, attn=attn, ffn=ffn)
    head = RnnOutputLayer(n_out=V, activation="softmax",
                          loss="sparse_mcxent", has_bias=False,
                          tied_embedding=True)
    embed = EmbeddingSequenceLayer(n_out=F, n_in=V)
    g.add_layer("embed", embed, "ids")
    g.add_layer("layer0", block(MultiHeadLatentAttention(
        n_out=F, n_heads=2, nope_dim=8, rope_dim=8, v_dim=8, kv_rank=16,
        q_rank=16, rotate=True, block_size=16),
        GatedMLP(n_out=F, hidden=64)), "embed")
    g.add_layer("layer1", block(
        GatedShortConv(n_out=F, conv_kernel=3),
        MoEFeedForward(n_out=F, n_experts=4, top_k=2, hidden=16,
                       activation="swish", gated=True, has_bias=False,
                       router="sigmoid", n_shared=1)), "layer0")
    g.add_layer("norm", RMSNormLayer(), "layer1")
    g.add_layer("head", head, "norm", params_of="embed")
    branch = dict(scope="mtp")
    g.add_vertex("mtp_ids", ShiftTimeSeriesVertex(steps=1), "ids", **branch)
    g.add_layer("mtp_embed", embed, "mtp_ids", params_of="embed", **branch)
    g.add_vertex("mtp_merge", MergeVertex(), "mtp_embed", "layer1",
                 **branch)
    g.add_layer("mtp_proj", LinearProjection(n_out=F), "mtp_merge",
                **branch)
    g.add_layer("mtp_head", head, "mtp_proj", params_of="embed", **branch)
    net = ComputationGraph(g.set_outputs("head", "mtp_head").build()).init()
    ids = jnp.zeros((2, 2, T), jnp.int32)
    mask = jnp.ones((2, 2, T), jnp.float32)
    return net, ((ids,), (ids, ids), None, (mask, mask))


def _mln_lm():
    from deeplearning4j_tpu.nn.conf.base import InputType
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingSequenceLayer, LayerNormLayer, MultiHeadAttention,
        RnnOutputLayer, TransformerBlock,
    )
    from deeplearning4j_tpu.nn.layers.attention import MoEFeedForward
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import AdamW
    b = (NeuralNetConfiguration.Builder().seed(1)
         .updater(AdamW(1e-3, weight_decay=0.1))
         .gradient_checkpointing(True).compute_dtype("bfloat16").list())
    b.layer(EmbeddingSequenceLayer(n_out=F, n_in=V))
    b.layer(TransformerBlock(n_out=F, n_heads=2, causal=True,
                             use_rope=True))
    b.layer(TransformerBlock(
        n_out=F, n_heads=2, norm="rms", has_bias=False,
        attn=MultiHeadAttention(n_out=F, n_heads=2, n_kv_heads=1,
                                causal=True, use_rope=True, qk_norm=True,
                                has_bias=False),
        ffn=MoEFeedForward(n_out=F, n_experts=4, top_k=2, hidden=16,
                           activation="swish", gated=True, has_bias=False,
                           router="sigmoid", n_shared=0,
                           experts_held=(0, 2))))
    b.layer(LayerNormLayer())
    b.layer(RnnOutputLayer(n_out=V, activation="softmax",
                           loss="sparse_mcxent"))
    net = MultiLayerNetwork(
        b.set_input_type(InputType.recurrent(1, T)).build()).init()
    ids = jnp.zeros((2, 2, T), jnp.int32)
    return net, (ids, ids, None, jnp.ones((2, 2, T), jnp.float32))


def _mixer_lm():
    """The zoo's `NemotronHLM` at tiny widths: blocks of ONE mixer
    (attention, experts in a latent with half of them held: one row tier,
    as `_mln_lm`'s share, whose walk is scoped whole; Mamba-2)."""
    from deeplearning4j_tpu.models import NemotronHLM
    net = NemotronHLM(
        vocab_size=V, seq_length=T, n_embd=F, pattern="*EM", mamba_heads=2,
        mamba_head_dim=8, state_dim=8, chunk=8, n_heads=2, n_kv_heads=1,
        head_dim=8, n_experts=8, top_k=2, expert_hidden=16, latent=8,
        shared_hidden=32, experts_held=(0, 4), block_size=16,
        compute_dtype="bfloat16").init()
    ids = jnp.zeros((2, 2, T), jnp.int32)
    return net, ((ids,), (ids,), None, (jnp.ones((2, 2, T), jnp.float32),))


def _streams_lm():
    """The zoo's `Xing4LM` at tiny widths: blocks on four residual streams
    (a dense one, one with experts half held) between the two ends, latent
    attention under YaRN."""
    from deeplearning4j_tpu.models import Xing4LM
    net = Xing4LM(
        vocab_size=V, seq_length=T, n_embd=F, n_layers=2, n_heads=2,
        q_rank=16, kv_rank=16, nope_dim=8, rope_dim=8, v_dim=8,
        rope_original_max_position=16, dense_hidden=64, n_experts=8,
        top_k=2, expert_hidden=16, experts_held=(0, 4), block_size=16,
        compute_dtype="bfloat16").init()
    ids = jnp.zeros((2, 2, T), jnp.int32)
    return net, ((ids,), (ids,), None, (jnp.ones((2, 2, T), jnp.float32),))


def _conv_graph():
    from deeplearning4j_tpu.nn.conf.base import InputType
    from deeplearning4j_tpu.nn.conf.graph_vertices import ElementWiseVertex
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.layers import (
        ActivationLayer, BatchNormalization, ConvolutionLayer,
        GlobalPoolingLayer, OutputLayer, SubsamplingLayer, ZeroPaddingLayer,
    )
    from deeplearning4j_tpu.nn.updaters import Nesterovs
    g = (NeuralNetConfiguration.Builder().seed(1)
         .updater(Nesterovs(1e-2, momentum=0.9)).l2(1e-4)
         .compute_dtype("bfloat16").graph_builder().add_inputs("input")
         .set_input_types(InputType.convolutional(8, 8, 3)))
    g.add_layer("pad", ZeroPaddingLayer(padding=(1, 1, 1, 1)), "input")
    g.add_layer("c1", ConvolutionLayer(n_out=8, kernel=(3, 3),
                                       has_bias=False), "pad")
    g.add_layer("bn1", BatchNormalization(), "c1")
    g.add_layer("relu1", ActivationLayer(activation="relu"), "bn1")
    g.add_layer("pool", SubsamplingLayer(kernel=(2, 2), stride=(2, 2)),
                "relu1")
    g.add_layer("c2", ConvolutionLayer(n_out=8, kernel=(1, 1),
                                       convolution_mode="same"), "pool")
    g.add_vertex("add", ElementWiseVertex(op="add"), "pool", "c2")
    g.add_layer("avg", GlobalPoolingLayer(pooling_type="avg"), "add")
    g.add_layer("out", OutputLayer(n_out=5, activation="softmax",
                                   loss="mcxent"), "avg")
    net = ComputationGraph(g.set_outputs("out").build()).init()
    return net, ((jnp.zeros((2, 2, 8, 8, 3), jnp.float32),),
                 (jnp.zeros((2, 2, 5), jnp.float32),), None, None)


#: what a path may hold and still be the step's own plumbing: the scan of
#: `build_step`, the closed call of the loss, the checkpoint's own ops
_PLUMBING = {"kstep", "while", "body", "cond", "closed_call", "checkpoint",
             "remat2", "rematted_computation", ""}


@pytest.mark.parametrize("build,parts,layers", [
    (_graph_lm, {"cast", "embed", "norm", "residual", "mla/proj", "mla/rope",
                 "mla/attn", "mla/out", "mlp/gated", "sconv/proj",
                 "sconv/mix", "moe/route", "moe/dispatch", "moe/experts",
                 "moe/shared", "moe/combine", "head/loss", "shift", "merge",
                 "proj", "opt/update"},
     {"embed", "layer0", "layer1", "norm", "head", "mtp_ids", "mtp_embed",
      "mtp_merge", "mtp_proj", "mtp_head"}),
    (_mln_lm, {"cast", "embed", "norm", "residual", "dense", "mha/proj",
               "mha/norm", "mha/rope", "mha/attn", "moe/route",
               "moe/dispatch", "moe/experts", "moe/combine", "head/loss",
               "opt/update"}, {"0", "1", "2", "3", "4"}),
    (_conv_graph, {"cast", "conv", "bn", "pool", "head/loss", "opt/update"},
     {"c1", "bn1", "pool", "c2", "avg", "out"}),
    (_mixer_lm, {"cast", "embed", "norm", "residual", "mha/proj", "mha/attn",
                 "ssd/proj", "ssd/conv", "ssd/scan", "ssd/out", "moe/route",
                 "moe/latent", "moe/dispatch", "moe/experts", "moe/shared",
                 "moe/combine", "head/loss", "opt/update"},
     {"embed", "layer0", "layer1", "layer2", "norm", "head"}),
    (_streams_lm, {"cast", "embed", "norm", "mhc/pre", "mhc/sinkhorn",
                   "mhc/post", "mhc/io", "mla/proj", "mla/rope", "mla/attn",
                   "mla/out", "mlp/gated", "moe/route", "moe/dispatch",
                   "moe/experts", "moe/shared", "moe/combine", "head/loss",
                   "opt/update"},
     {"embed", "streams", "layer0", "layer1", "sum", "norm", "head"}),
], ids=["graph_lm", "multilayer_lm", "conv_graph", "mixer_lm",
        "streams_lm"])
def test_every_op_of_the_scan_step_has_a_layer_and_a_part(build, parts,
                                                          layers):
    net, operands = build()
    compiled = net._make_scan_step().lower(
        net.params, net.opt_state, net.state, *operands,
        jnp.zeros((2, 2), jnp.uint32)).compile()
    rows = xla_ledger.compiled_ops(compiled)
    leaves = [r for r in rows
              if r["opcode"] not in xla_ledger.CONTAINER_OPCODES
              and r["scope"]]
    assert len(leaves) > 40
    homeless = []
    for r in leaves:
        if r["layer"] and r["part"]:
            continue
        if r["layer"] is None and r["part"] == "opt/update":
            continue
        path = scopes._WRAPPERS.sub("", r["scope"]).split("/")
        if r["layer"] is None and r["part"] is None:
            # the step's own plumbing: nothing but the scan, the loss's
            # closed call and at most the one primitive at the end
            if set(path[:-1]) <= _PLUMBING:
                continue
        if r["layer"] and r["opcode"] == "copy" and path[-1] == "remat2":
            # XLA's copy of a checkpoint region's operand: the layer's,
            # and no part's
            continue
        homeless.append((r["name"], r["scope"]))
    assert not homeless, homeless
    # every part the net's layers enter reaches the compiled program (read
    # off every op_name of the text: which op is a fusion's root, and so
    # names its row, is the backend's choice)
    seen = {scopes.parse(n)[1] for n in re.findall(
        r'op_name="([^"]*)"', compiled.as_text())}
    assert seen >= parts, parts - seen
    assert {r["layer"] for r in leaves if r["layer"]} >= layers
    # a graph vertex's scope stays in the path, outside the layer's
    if build is _graph_lm:
        branch = [r for r in leaves if (r["layer"] or "").startswith("mtp_")]
        assert branch and all("mtp" in scopes._WRAPPERS.sub(
            "", r["scope"]).split("/") for r in branch)
        assert any(r["part"] == "head/loss" and r["layer"] == "mtp_head"
                   for r in branch)
    # no part's code calls a layer that enters another part
    assert not any("mha/norm/norm" in r["scope"] for r in leaves)
    # what a checkpointed layer makes again is marked, the optimizer's
    # work is not
    if build is not _conv_graph:
        assert any(r["recomputed"] for r in leaves)
    assert not any(r["recomputed"] for r in leaves
                   if r["part"] == "opt/update")


# --------------------------------------------------- the ledger's record
def _tiny_fit(scan_steps=2):
    from deeplearning4j_tpu.data.iterator import ArrayDataSetIterator
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.conf.base import InputType
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.Builder().seed(3).list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(5)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, 5)).astype("float32")
    y = np.eye(3, dtype="float32")[rng.integers(0, 3, 16)]
    net.fit(ArrayDataSetIterator(x, y, batch_size=4), scan_steps=scan_steps)
    return net


def test_with_the_ledger_off_the_parser_never_runs(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("the parser ran with the ledger off")
    monkeypatch.setattr(xla_ledger, "compiled_op_scopes", boom)
    monkeypatch.setattr(xla_ledger, "parse_hlo_ops", boom)
    _tiny_fit()                              # the scan path
    assert xla_ledger.records() == []


def test_the_record_keeps_the_table_and_its_views(tmp_path):
    xla_ledger.enable_ledger()
    _tiny_fit()
    (rec,) = [r for r in xla_ledger.records() if r.name == "mln/scan_step"]
    assert rec.ops and all(
        set(row) == {"name", "opcode", "kind", "computation", "parent",
                     "scope", "layer", "part", "recomputed", "direction",
                     "dot_flops", "bytes_out", "bytes_in"}
        for row in rec.ops)
    # op_scopes is what it was: instruction -> op_name, strings only
    assert rec.op_scopes == {r["name"]: r["scope"] for r in rec.ops
                             if r["scope"]}
    assert all(isinstance(v, str) for v in rec.op_scopes.values())
    assert xla_ledger.kernel_calls(rec.op_scopes) == {}
    assert any(r["part"] == "dense" and r["layer"] == "0" for r in rec.ops)
    assert any(r["part"] == "head/loss" and r["layer"] == "1"
               for r in rec.ops)
    assert any(r["part"] == "opt/update" and r["layer"] is None
               for r in rec.ops)
    # the saved ledger carries the rows: a profile names an instruction,
    # the ledger says what it is
    path = str(tmp_path / "ledger.json")
    xla_ledger.save_ledger(path)
    with open(path) as f:
        doc = json.load(f)
    (saved,) = [p for p in doc["programs"] if p["name"] == "mln/scan_step"]
    assert saved["ops"] == rec.ops
    by = {r["name"]: r for r in saved["ops"]}
    assert all(r["parent"] is None or r["parent"] in by
               for r in saved["ops"])
    # a record whose text was not parsed says nothing
    rec.op_scopes = {}
    assert rec.ops == [] and "ops" not in rec.to_json()
