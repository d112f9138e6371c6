"""The benchmark's correctness check on the DeepSeek-LLM-7B layout: no
bias on the q, k and v projections, and as many KV heads as query heads.

The benchmark's configuration files must describe the weights the program
builds, and ``bench.run.run_cell`` must serve a tiny configuration of that
layout through the serving front-end and the fused kernels (interpret
mode on the CPU), score what it served against the plain float32
reference (``bench/reference.py``), and tell a sound serve step from a
broken or lower-precision one.
"""
import inspect
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "bench" / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import reference, spec, weights  # noqa: E402
from test_bench import broken, make_root  # noqa: E402
from repro import configs  # noqa: E402
from repro.models import layers, lm  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in BENCH["configs"]}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_benchmark_config_is_the_programs_model(name):
    """Each configuration file states weights of exactly the layout, rotary
    base and norm epsilon the program runs, at the published widths."""
    conf = spec.load_json(ROOT / CONFIGS[name]["file"])
    cfg = configs.get(conf["arch"])
    if conf.get("arch_overrides"):
        cfg = cfg.with_(**conf["arch_overrides"])
    assert weights.layout(lm.param_specs(cfg, jnp.bfloat16)) == \
        reference.expected_layout(conf)
    assert float(cfg.rope_theta) == conf["rope_theta"]
    eps = inspect.signature(layers.rms_norm).parameters["eps"].default
    assert eps == conf["rms_norm_eps"]
    assert CONFIGS[name]["reduced"] == conf["reduced"] == []


def test_deepseek_config_is_published_mha_without_bias():
    conf = spec.load_json(ROOT / CONFIGS["deepseek-llm-7b-inplace"]["file"])
    m = reference.dims(conf)
    assert (m["d"], m["ff"], m["layers"], m["h"], m["kv"], m["hd"],
            m["vocab"]) == (4096, 11008, 30, 32, 32, 128, 102400)
    assert not m["bias"] and "arch_overrides" not in conf
    n = sum(math.prod(s) for s in reference.expected_layout(conf).values())
    assert n == 6_910_365_696
    cell = spec.load_cell(ROOT, "ds7b-chat")
    assert cell.chips == 1 and cell.config_name == "deepseek-llm-7b-inplace"
    assert {m["name"] for m in cell.per_layer} == \
        {m["name"] for m in BENCH["per_layer"]}


@pytest.fixture(scope="module")
def own_cache(tmp_path_factory):
    """The harness turns on JAX's persistent cache for every program; keep
    its entries out of the checkout and its settings out of later tests."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {n: getattr(jax.config, n) for n in names}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR",
                  str(tmp_path_factory.mktemp("jax_cache")))
        yield
    for n, v in was.items():
        jax.config.update(n, v)


def serve(cell, **kw):
    from bench import run
    return run.run_cell(cell, seed=kw.pop("seed", 2**32 + 3),
                        seconds=kw.pop("seconds", 3.0), trace=False, **kw)


@pytest.fixture(scope="module")
def tiny_ds(tmp_path_factory, own_cache):
    root = make_root(tmp_path_factory.mktemp("tiny-ds"),
                     config="tiny-ds.json")
    return spec.load_cell(root, "tiny-cell")


def test_tiny_deepseek_cell_is_correct_and_catches_a_wrong_token(tiny_ds):
    res = serve(tiny_ds)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["requests_checked"]["value"] > 0
    assert res["checks"]["ecc_flags"]["value"] == 0
    res = serve(tiny_ds, wrap=broken("altered_token"))
    assert not res["correct"], res["checks"]


def test_tiny_deepseek_cell_catches_a_stale_cache(tiny_ds):
    res = serve(tiny_ds, wrap=broken("unchanged_state"))
    assert not res["correct"], res["checks"]


def test_lower_precision_control_reads_worse_without_bias(tmp_path,
                                                          own_cache):
    """At the tiny widths the program and the int8-activation control
    both read every gap 0; at the wide test widths the control reads a
    larger mean gap than the program on the same seed."""
    cell = spec.load_cell(make_root(tmp_path, rate=8.0,
                                    config="tiny-ds-wide.json"), "tiny-cell")
    for seed in (2**32 + 11, 2**32 + 12):
        prog = serve(cell, seed=seed, seconds=8.0)["readings"]
        ctrl = serve(cell, seed=seed, seconds=8.0,
                     control="int8-act")["readings"]
        assert ctrl["mean_logit_gap"] > prog["mean_logit_gap"], (prog, ctrl)
