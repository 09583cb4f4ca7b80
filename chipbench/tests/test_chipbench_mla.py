"""The DeepSeek-V2 cell's pieces: its shape counts against a hand count,
its three readers on hand-made readings, its files found by name, and
its driver run end to end on the CPU at the smoke size."""
import json
import os

import pytest

from chipbench import counts_mla
from chipbench.devtrace import ProgramTime, Reduction
from chipbench.harness import HERE, Manifest, Readings, run_cell
from chipbench.peaks import least_seconds, peaks_for

M = Manifest()
P = peaks_for("TPU v5 lite")
DATA = os.path.join(os.path.dirname(__file__), "data")
TINY = Manifest(os.path.join(DATA, "BENCHMARK.json"),
                search=(DATA,)).json("configs", "tiny-mla")
CELL = "deepseek-v2-lite.doc-qa"


def test_counts_against_a_hand_count():
    """d 64, 4 heads, latent 32 + RoPE 8, qk 16, v 16, dense ff 96, 8
    experts of 32 (1 shared), 1 dense + 2 expert layers, vocab 512."""
    attn = 64 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 4 * 16 * 64
    assert counts_mla.attn_params(TINY) == attn == 16896
    assert counts_mla.expert_params(TINY) == 3 * 64 * 32
    dense = 3 * attn + 3 * 64 * 96 + 2 * (64 * 8 + 6144) + 64 * 512
    assert counts_mla.dense_matmul_params(TINY) == dense == 115200
    assert counts_mla.norm_params(TINY) == 3 * (2 * 64 + 32) + 64
    assert counts_mla.latent_bytes_per_token(TINY) == 3 * 40 * 2
    # slots at positions 9 and 19 attend 10 and 20 rows: 2 x 4 heads x
    # (32 + 8 + 32) per row per layer; 3 picks fell on held experts
    assert counts_mla.decode_step_flops(TINY, [9, 19], 3) == \
        2 * dense * 2 + 2 * 6144 * 3 + 3 * 576 * 30
    # 5 held experts picked over the layers, 3 distinct blocks of 16 rows
    assert counts_mla.decode_step_bytes(TINY, 2, 3, 16, 5) == \
        2 * (dense + 544 + 5 * 6144 + 2 * 64) + 240 * 16 * 3 + 240 * 2


def test_counts_cover_the_programs_parameters():
    """Every parameter the program holds for the cut is a weight every
    token uses, a norm, a held expert or the embedding table."""
    cfg = M.module("drivers", "serve_mla").program_config(TINY)
    held = TINY["n_routed_experts_held"] * counts_mla.expert_layers(TINY)
    assert cfg.param_count() == (
        counts_mla.dense_matmul_params(TINY) + counts_mla.norm_params(TINY)
        + held * counts_mla.expert_params(TINY) + 512 * 64)


def _red(programs, busy=0.8, window=1.0):
    return Reduction(window, busy, {k: ProgramTime(*v)
                                    for k, v in programs.items()}, [], [])


def _read(name, trace, data, config=TINY):
    return M.module("metrics", name).read(
        Readings(config, trace, P, 10.0, data))


def test_decode_roofline_mla_is_least_over_device_time_per_step():
    c = M.json("configs", "deepseek-v2-lite")
    steps = [([16400, 16900], 1060, 90, 60), ([16401, 16901], 1060, 96, 62)]
    least = sum(least_seconds(counts_mla.decode_step_flops(c, pos, pk),
                              counts_mla.decode_step_bytes(c, 2, b, 16, hit),
                              P) for pos, b, pk, hit in steps) / 2
    red = _red({"_decode": (4, 4 * 0.05)})
    got = _read("decode_roofline.mla", red, {"mla_decode_steps": steps}, c)
    assert got == pytest.approx(100 * least / 0.05)
    assert 0 < got < 100
    assert _read("decode_roofline.mla", _red({}),
                 {"mla_decode_steps": steps}, c) is None
    assert _read("decode_roofline.mla", red, {"mla_decode_steps": []},
                 c) is None


def test_mfu_mla_counts_held_picks_at_the_traced_share():
    data = {"tokens_computed": 1000, "moe_assignments": 1200,
            "moe_assignments_here": 150}
    held = 1000 * 2 * 2 * 150 / 1200       # tokens x top-k x expert layers
    want = 2 * (1000 * 115200 + held * 6144)
    assert _read("mfu.mla", None, data) == pytest.approx(
        100 * want / (10.0 * 197e12))
    assert _read("mfu.mla", None, dict(data, moe_assignments=0)) is None
    assert _read("mfu.mla", None, dict(data, tokens_computed=0)) is None


def test_expert_fill():
    assert _read("expert_fill", None, {"moe_assignments_here": 48,
                                       "moe_expert_rows": 512}) == \
        pytest.approx(9.375)
    assert _read("expert_fill", None, {"moe_assignments_here": 0,
                                       "moe_expert_rows": 0}) is None
    assert _read("expert_fill", None, {}) is None


def test_the_cell_finds_its_config_driver_reference_and_traffic():
    w = M.workload(CELL)
    c = M.json("configs", w["config"])
    mix = M.json("traffic", w["traffic"])
    assert c["kind"] == "serve_mla" and w["chips"] == 1
    assert hasattr(M.module("drivers", c["kind"]), "run")
    assert hasattr(M.module("models", c["reference"]), "forward")
    assert {"server", "check", "system_prompts"} <= set(mix)
    names = {m["name"] for m in M.per_layer(CELL)}
    assert {"decode_roofline.mla", "mfu.mla", "expert_fill",
            "idle_share.serve", "host_share.serve"} == names
    for n in names:
        assert hasattr(M.module("metrics", n), "read")
    assert {m["name"] for m in M.end_to_end(CELL)} == {
        "tokens_per_s", "tpot_p95_ms", "setup_s"}
    # the reduced keys are the only ones that differ from the published
    entry = next(e for e in M.data["configs"] if e["name"] == w["config"])
    assert [k for k, v in c["published"].items()
            if k in c and c[k] != v] == ["num_hidden_layers"]
    assert set(entry["reduced"]) == {"num_hidden_layers",
                                     "n_routed_experts_held"}
    # the arena holds every document and each slot's whole lifetime
    srv, sysp = mix["server"], mix["system_prompts"]
    assert srv["total_tokens"] >= sysp["count"] * sysp["len"] + \
        srv["batch"] * (mix["prompt_len"][1] + mix["output_len"][1])
    assert srv["max_seq"] == sysp["len"] + mix["prompt_len"][1] + \
        mix["output_len"][1]


def test_driver_refuses_a_width_the_program_does_not_have():
    drv = M.module("drivers", "serve_mla")
    with pytest.raises(ValueError, match="differs"):
        drv.program_config(dict(TINY, kv_lora_rank=48))
    with pytest.raises(ValueError, match="serves only"):
        drv.program_config(dict(TINY, q_lora_rank=16))


# max_gap: the bf16 program read 0 to 0.107 here (16 seeds and windows of
# 0.3 and 1 s: which requests the check samples depends on the window), the
# fp8 control 0.467 to 1.61 (8); the limit lies between, with room.
MIX = {"n_requests": 512, "block": 8, "arrival": "all_at_start",
       "prompt_len": [8, 24], "output_len": [4, 8],
       "system_prompts": {"count": 2, "len": 64, "zipf_s": 1.0},
       "server": {"batch": 4, "max_seq": 96, "total_tokens": 1024,
                  "chunk_tokens": 32, "prefix_cache": True},
       "check": {"max_gap": 0.25, "sample": 3}}


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    """The smoke-size cell, made only of new files beside the benchmark's
    own, as the harness finds a cell."""
    d = tmp_path_factory.mktemp("mla")
    (d / "configs").mkdir()
    (d / "traffic").mkdir()
    (d / "configs" / "tiny-mla.json").write_text(json.dumps(TINY))
    (d / "traffic" / "tiny-doc.json").write_text(json.dumps(MIX))
    cell = "tiny-mla.doc"
    e2e = [{"name": n, "unit": u, "better": b, "bound": 0.1,
            "source": "host_clock", "workloads": [cell]}
           for n, u, b in (("tokens_per_s", "tokens/s", "higher"),
                           ("tpot_p95_ms", "ms", "lower"),
                           ("setup_s", "s", "lower"))]
    (d / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": cell, "config": "tiny-mla",
                       "traffic": "tiny-doc", "chips": 1, "why": "test"}],
        "end_to_end": e2e, "per_layer": []}))
    return cell, Manifest(d / "BENCHMARK.json", search=(d, HERE))


def _run(tiny_cell, **kw):
    cell, manifest = tiny_cell
    return run_cell(cell, 2 ** 35 + 9, 1.0, False, manifest=manifest,
                    require_tpu=False, **kw)


def test_tiny_cell_runs_correct(tiny_cell):
    line = _run(tiny_cell)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "tpot_p95_ms", "setup_s"}


def test_tiny_cell_control_fails_the_limit(tiny_cell):
    """The program's widest gap below the float32 reference's best logit
    stays under the cell's limit; the fp8 control's goes over it."""
    checks = _run(tiny_cell, control=True)["checks"]
    limit = MIX["check"]["max_gap"]
    assert checks["max_gap"]["value"] <= limit
    assert checks["control_max_gap"]["value"] > limit


def test_tiny_cell_catches_a_dropped_expert_share(tiny_cell, monkeypatch):
    """The held experts' part left out of every expert layer."""
    from repro.models import moe
    real = moe._local_moe

    def without_experts(*a, **k):
        out, aux, picks = real(*a, **k)
        return out * 0, aux, picks
    monkeypatch.setattr(moe, "_local_moe", without_experts)
    assert _run(tiny_cell)["correct"] is False
