"""The Qwen3-Next configuration holds its published model: all 48 layers at
the catalog's widths with 16 of 512 experts (one chip of 32-way expert
parallelism), the parameter count of the model card, and a cell that
reports what its layers move."""
import json

import pytest

from benchfix import ROOT
from bench import run as br

CFG = json.loads((ROOT / "bench/configs/qwen1.5-0.5b-qwen3-next-80b-a3b.json")
                 .read_text())
CELL = "qwen1.5-0.5b-qwen3-next-80b-a3b.mixed-saturated"
ARCH = br.arch_module("qwen3_next")
TARGET = CFG["members"][-1]


def test_published_parameter_count():
    """79.674e9 with all 512 experts: the card says "80B total", rounded,
    and counts no MTP layer (left out here, as the published forward
    leaves it); the count is the layout's, so it is exact."""
    published = dict(TARGET["config"], **TARGET["published"])
    assert ARCH.published_params(published) == 79_674_391_296
    assert ARCH.published_params(published) == pytest.approx(80e9, rel=0.01)
    # as run: 16 experts a layer, the router still 512 wide
    assert ARCH.published_params(TARGET["config"]) == 4_780_899_072


def test_the_cut_is_the_experts_held_and_nothing_else():
    from repro.configs.qwen3_next_80b_a3b import PUBLISHED
    hf = TARGET["config"]
    assert CFG["reduced"] == ["num_experts"]
    assert hf["num_experts"] == 16 and hf["router_experts"] == 512
    assert TARGET["published"] == {"num_experts": 512}
    for key, v in PUBLISHED.items():
        if key != "num_experts":
            assert hf[key] == v and CFG[key] == v, key
    cfg = ARCH.program_config(TARGET)
    assert cfg.num_layers == 48 and cfg.moe.num_experts == 512
    assert (cfg.moe.held_start, cfg.moe.num_held, cfg.moe.top_k) == (0, 16, 10)
    assert cfg.recurrent and cfg.supports_paged and not cfg.supports_tree
    assert CFG["slots"] >= 4
    # the draft is the Qwen1.5-0.5B of the first configuration, unchanged
    first = json.loads((ROOT / "bench/configs/qwen1.5-0.5b-1.8b-4b.json")
                       .read_text())
    assert CFG["members"][0] == first["members"][0]
    assert CFG["planting"] == first["planting"]


def test_flops_count_the_held_share_of_the_routed_experts():
    hf = TARGET["config"]
    base = ARCH.flops_per_token(hf, 0.0)
    # the attention products: 12 layers, 16 heads of 256, QK and PV
    assert ARCH.flops_per_token(hf, 100.0) - base == pytest.approx(
        4.0 * 12 * 16 * 256 * 100)
    more = dict(hf, num_experts=32)       # twice the experts held
    routed = 10 * 16 / 512 * 3 * 2048 * 512 * 48 * 2
    assert ARCH.flops_per_token(more, 0.0) - base == pytest.approx(routed)


def test_the_cell_reports_its_layers():
    _, cfg, mix, e2e, per_layer = br.load_cell(CELL)
    assert cfg["name"] == CFG["name"] and mix["mode"] == "closed_batches"
    assert {m["name"] for m in e2e} == {"tokens_per_s", "setup_s"}
    names = {m["name"] for m in per_layer}
    assert {"gdn_device_share", "moe_device_share", "kv_device_share",
            "cycle_ms", "mfu", "device_idle_share",
            "host_syncs_per_cycle"} <= names


class _Run:
    def __init__(self, scoped):
        self.scoped_busy = scoped


def test_scope_readers_read_their_scope_and_are_silent_without_it():
    gdn, moe = br.reader("gdn_device_share"), br.reader("moe_device_share")
    scoped = {"jit(fused_1L_w1_p2)/decode/while/body/gdn/gdn.delta/mul": 3.0,
              "jit(fused_1L_w1_p2)/decode/while/body/gdn/dot_general": 1.0,
              "jit(fused_1L_w1_p2)/decode/while/body/moe/dot_general": 4.0,
              "jit(fused_1L_w1_p2)/decode/while/body/kv_gather/gather": 2.0}
    assert gdn(_Run(scoped)) == pytest.approx(40.0)
    assert moe(_Run(scoped)) == pytest.approx(40.0)
    plain = {"jit(fused_1L_w1_p2)/decode/while/body/dot_general": 1.0}
    assert gdn(_Run(plain)) is None and moe(_Run(plain)) is None
