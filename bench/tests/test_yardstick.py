"""The yardstick's arithmetic: peaks, useful FLOPs, arrivals."""

import jax
import numpy as np
import pytest

from bench import reference
from bench.flops import stage_flops
from bench.harness import (BENCH, Cell, derived_seed, load_json,
                           load_module, metric_reader, peaks_for)
from bench.tests.tiny import config_file

# useful FLOPs of one query at each stage, as the counts gave them when
# they still sat in bench/flops.py: moving them into the families keeps
# every number that mfu_pct reads
USEFUL_FLOPS = {"face-recognition": 85_936_308_224,
                "image-enhancement": 30_021_779_456,
                "semantic-understanding": 116_620_001_280,
                "image-generation": 80_042_786_816}


def test_peaks_lookup_by_device_kind():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks_for("TPU v9 imaginary")


def _stage_models():
    for name in ("img-to-img", "text-to-img"):
        for st in load_json(config_file(name))["stages"]:
            yield st


@pytest.mark.parametrize("stage", list(_stage_models()),
                         ids=lambda st: f"{st['arch']}-{st['seq_len']}")
def test_matmul_params_match_the_program_weights(stage):
    """Every matrix the step multiplies by is counted once: the count
    equals the program's block weights of rank 2 and up (per layer; the
    depthwise convolution excepted)."""
    from repro.configs import get_config
    from repro.models import abstract_params
    cfg = get_config(stage["arch"])
    blocks = abstract_params(cfg)["blocks"]
    total = 0
    for pos in blocks:
        for path, leaf in jax.tree_util.tree_leaves_with_path(pos):
            name = jax.tree_util.keystr(path)
            if leaf.ndim >= 3 and "conv_w" not in name:
                total += int(np.prod(leaf.shape))
    assert reference.family(stage).matmul_params(stage["model"]) == total


@pytest.mark.parametrize("stage", list(_stage_models()),
                         ids=lambda st: st["name"])
def test_stage_flops_unchanged(stage):
    assert stage_flops(stage) == USEFUL_FLOPS[stage["name"]]


def test_family_refuses_a_kind_it_does_not_serve():
    """An attention model with sparse experts is not the transformer
    family's dense model: its count and its reference both refuse it."""
    st = next(_stage_models())
    moe = {**st, "model": {**st["model"], "mlp_pattern": ["moe"]}}
    with pytest.raises(ValueError, match="'moe'"):
        stage_flops(moe)
    with pytest.raises(ValueError, match="'moe'"):
        reference.last_logits(moe, 0, np.zeros((1, 4), np.int32))


@pytest.mark.parametrize("path", [BENCH / "flops.py",
                                  BENCH / "reference" / "__init__.py"],
                         ids=lambda p: p.name)
def test_dispatch_names_no_block_kind(path):
    """Every count and every reference lives in a family module found by
    the stage's name for it: the dispatch lists no kind."""
    text = path.read_text()
    for kind in ("attn", "mlstm", "slstm", "dense", "moe", "none"):
        assert f'"{kind}"' not in text and f"'{kind}'" not in text, kind


@pytest.mark.parametrize("arch,seq", [("qwen3-0.6b", 96),
                                      ("qwen1.5-0.5b", 48),
                                      ("qwen1.5-0.5b", 128)])
def test_stage_flops_against_analytic_costs(arch, seq):
    """For the dense models, the useful count is the program's analytic
    prefill count less the head at all but the last position and less the
    masked half of the attention scores."""
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.launch.roofline import analytic_costs
    cfg = get_config(arch)
    shp = InputShape(name="bench", kind="prefill", seq_len=seq,
                     global_batch=1)
    full = analytic_costs(cfg, shp)["flops"]
    st = next(st for st in _stage_models() if st["arch"] == arch)
    m = st["model"]
    hd, nh = m["head_dim"], m["num_heads"]
    masked = 4.0 * nh * hd * (seq * seq - seq * (seq + 1) / 2) \
        * m["num_layers"]
    head = 2.0 * (seq - 1) * m["d_model"] * m["vocab_size"]
    assert stage_flops({**st, "seq_len": seq}) == pytest.approx(
        full - head - masked, rel=1e-12)


def test_img_to_img_query_flops_near_the_weight_estimate():
    cfg = load_json(BENCH / "configs" / "img-to-img.json")
    q3, q15 = (stage_flops(st) for st in cfg["stages"])
    # 2 x matrix weights x tokens: 84.5 and 29.6 GFLOP, plus head and
    # attention
    assert 84.5e9 < q3 < 87e9
    assert 29.6e9 < q15 < 31e9


def test_poisson_arrivals_fixed_work_per_seed():
    poisson = load_module(BENCH / "arrivals" / "poisson.py")
    a = poisson.arrival_times(100.0, 10.0, np.random.default_rng(1))
    b = poisson.arrival_times(100.0, 10.0, np.random.default_rng(2))
    assert len(a) == len(b) == 1000
    assert 0.0 < a[0] and np.all(np.diff(a) > 0) and a[-1] < 10.0
    # the same set of gaps, in another order
    gaps = np.diff(a, prepend=0.0)
    np.testing.assert_allclose(np.sort(gaps),
                               np.sort(np.diff(b, prepend=0.0)), rtol=1e-9)
    assert not np.allclose(a, b)
    # exponential: coefficient of variation near 1
    assert 0.9 < gaps.std() / gaps.mean() < 1.1


def test_derived_seed_uses_the_whole_seed():
    big = 2**33 + 5
    assert derived_seed(big, "w", 0) != derived_seed(5, "w", 0)
    assert derived_seed(big, "w", 0) == derived_seed(big, "w", 0)
    assert 0 <= derived_seed(big, "w", 1) < 2**31


def _mfu_record(programs, completed=800, batches=100):
    cfg = load_json(BENCH / "configs" / "img-to-img.json")
    return {"trace": {"programs": programs}, "batch": 8,
            "completed": completed, "batches": batches,
            "stages": Cell("img-to-img.poisson-knee80").stages,
            "peaks": peaks_for("TPU v5 lite")}, cfg["stages"]


def test_mfu_counts_each_step_program_as_its_stage():
    """Executions x rows x the stage's useful FLOPs, over the programs'
    device time; a program touching no stage's prompt shape is left out
    of both sides, and padding rows are scaled away by the batch fill."""
    read = load_module(metric_reader(BENCH, "mfu_pct")).read
    programs = {"jit_run(1)": {"count": 100, "seconds": 0.6,
                               "shapes": [[8, 96]]},
                "jit_run(2)": {"count": 100, "seconds": 0.3,
                               "shapes": [[8, 48]]},
                "jit_run(3)": {"count": 5, "seconds": 9.0,
                               "shapes": [[8, 1]]}}
    record, stages = _mfu_record(programs)
    flops = 100 * 8 * sum(stage_flops(st) for st in stages)
    assert read(record) == pytest.approx(100 * flops / (0.9 * 197e12))
    half, _ = _mfu_record(programs, completed=400)
    assert read(half) == pytest.approx(read(record) / 2)
    record["trace"]["programs"] = {"jit_run(3)": programs["jit_run(3)"]}
    assert read(record) is None


def test_split_metric_names_share_one_reader():
    for name in ("mfu_pct.over", "device_idle_pct.over"):
        assert metric_reader(BENCH, name) == \
            BENCH / "metrics" / f"{name.split('.')[0]}.py"
    assert metric_reader(BENCH, "latency_p99_ms") == \
        BENCH / "metrics" / "latency_p99_ms.py"
