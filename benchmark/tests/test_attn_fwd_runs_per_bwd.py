"""`attn_fwd_runs_per_bwd` on the instruction tables the two LM cells'
tests plant: the flash forward's instructions over its backward's."""
import types

import pytest

import test_glm_4_7_flash as glm
import test_kimi_linear as kimi
from benchmark.lib import manifest

NAME = "attn_fwd_runs_per_bwd"
BWD = {"flash_bwd_dq.3":
       "jit(kstep)/while/body/transpose(jvp(mla/attn))/flash_bwd_dq",
       "flash_bwd_dkv.3":
       "jit(kstep)/while/body/transpose(jvp(mla/attn))/flash_bwd_dkv"}
NO_FLASH = {n: s for n, s in kimi.SCOPES.items() if "flash" not in n}


def _read(scopes):
    system = types.SimpleNamespace(STEP_PROGRAM="jit_kstep",
                                   op_scopes=lambda: scopes)
    return manifest.load_module("metrics", NAME).read({"system": system})


@pytest.mark.parametrize("scopes,reads", [
    (glm.SCOPES | BWD, 2.0),     # two forward instructions, one backward
    (kimi.SCOPES, 1.0),          # one and one
    (kimi.SCOPES | {"flash_fwd.8": kimi.SCOPES["flash_fwd.7"]}, 2.0),
    (glm.SCOPES, None),          # no backward kernel: nothing to divide by
    (NO_FLASH, None), ({}, None), (None, None)])
def test_forward_instructions_over_backward_instructions(scopes, reads):
    assert _read(scopes) == reads


def test_an_adapter_without_the_map_reads_nothing():
    bare = types.SimpleNamespace(STEP_PROGRAM="jit_kstep")
    assert manifest.load_module("metrics", NAME).read({"system": bare}) \
        is None


def test_the_manifest_lists_it_for_the_two_lm_cells():
    entry = next(m for m in glm.M["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "x", "better": "lower",
        "source": "program_counter", "layer": "compiled step",
        "moves": "train_examples_per_s",
        "workloads": [kimi.CELL, glm.CELL]}
    for cell in (kimi.CELL, glm.CELL):
        assert NAME in {m["name"] for m in manifest.Cell(glm.M, cell).per_layer}
