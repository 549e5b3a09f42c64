"""End-to-end system behaviour: the dry-run launcher (subprocess, tiny
mesh) and the sharding recipe's structural guarantees."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_dryrun(tmp_path, arch, shape):
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": SRC,
        "DRYRUN_DEVICES": "8",
        "REPRO_MESH_OVERRIDE": "2,4",
        "DRYRUN_DIR": str(tmp_path),
    })
    code = (
        "import repro.launch.dryrun as d\n"
        "import repro.configs.registry as reg\n"
        "d.get_config = reg.get_smoke_config\n"
        "from repro.configs.base import INPUT_SHAPES, InputShape\n"
        "INPUT_SHAPES['train_4k'] = InputShape('train_4k', 128, 8, 'train')\n"
        "INPUT_SHAPES['decode_32k'] = InputShape('decode_32k', 256, 8, 'decode')\n"
        "INPUT_SHAPES['prefill_32k'] = InputShape('prefill_32k', 256, 8, 'prefill')\n"
        "INPUT_SHAPES['long_500k'] = InputShape('long_500k', 2048, 1, 'decode')\n"
        f"rec = d.run_one('{arch}', '{shape}', False, out_dir='{tmp_path}', force=True)\n"
        "assert rec['status'] == 'ok', rec.get('error', '')[-2000:]\n"
    )
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    with open(os.path.join(str(tmp_path),
                           f"{arch}__{shape}__singlepod.json")) as f:
        return json.load(f)


@pytest.mark.slow
@pytest.mark.parametrize("arch,shape", [
    ("gemma2-9b", "train_4k"),
    ("phi3.5-moe-42b-a6.6b", "decode_32k"),
    ("xlstm-1.3b", "long_500k"),
])
def test_dryrun_lowers_and_compiles(tmp_path, arch, shape):
    rec = _run_dryrun(tmp_path, arch, shape)
    assert rec["status"] == "ok"
    r = rec["roofline"]
    assert r["hlo_flops_per_device"] > 0
    assert r["t_compute_s"] >= 0 and r["t_memory_s"] > 0
    assert rec["collectives"]["total_bytes_per_device"] > 0
    assert r["dominant"] in ("compute", "memory", "collective")


def test_sharding_recipe_divisibility():
    """Every full config's parameter sharding must only split divisible
    dims (replicate otherwise) — structural check without a real mesh."""
    import jax
    from repro.configs import ARCH_IDS, get_config
    from repro.launch import specs as S
    from repro.models import build

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
        devices = None

    captured_orig = S.NamedSharding

    def fake_ns(mesh, spec):
        return spec

    S.NamedSharding = fake_ns
    try:
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            model = build(cfg)
            shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            tree = S.param_shardings(FakeMesh(), shapes, cfg)
            flat_specs = dict(S._tree_paths(tree))
            flat_shapes = dict(S._tree_paths(shapes))
            n_sharded = 0
            for path, spec in flat_specs.items():
                dims = flat_shapes[path].shape
                for dim, ax in zip(dims, tuple(spec)):
                    if ax is None:
                        continue
                    n_sharded += 1
                    n = 16
                    assert dim % n == 0, (arch, path, dims, spec)
            assert n_sharded > 0, f"{arch}: nothing sharded"
    finally:
        S.NamedSharding = captured_orig


@pytest.mark.parametrize("env_dir", [None, "/srv/jax-cache"])
def test_compile_cache_placement(env_dir, monkeypatch):
    """The cache goes where JAX_COMPILATION_CACHE_DIR says, and nowhere
    else; unset, it goes to the fixed <checkout>/.jax_cache."""
    from repro.launch import compile_cache as cc
    if env_dir is None:
        monkeypatch.delenv(cc.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(cc.ENV_VAR, env_dir)
    updates = {}
    monkeypatch.setattr(cc.jax.config, "update",
                        lambda name, value: updates.__setitem__(name, value))
    want = env_dir or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    assert cc.setup_compile_cache() == want
    assert updates["jax_compilation_cache_dir"] == want
