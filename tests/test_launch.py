"""Launch-layer units: dry-run admissibility, roofline terms, zero-1 LMO
partition rule, head-padding adaptation, mesh helpers."""
import dataclasses

from repro.configs import ARCHS, SHAPES, get_config
from repro.launch.hlo_analysis import roofline_terms


def test_long500k_admissibility_matches_design():
    """Sub-quadratic gate: exactly the 4 archs with recurrent state or a
    sliding window run long_500k (DESIGN.md §Arch-applicability)."""
    runs = {a for a in ARCHS if a != "nanogpt-124m"
            and get_config(a).sub_quadratic}
    assert runs == {"xlstm-1.3b", "recurrentgemma-2b", "mixtral-8x7b",
                    "starcoder2-15b"}


def test_skip_reason():
    from repro.launch.dryrun import skip_reason
    assert skip_reason(get_config("granite-3-2b"),
                       SHAPES["long_500k"]) is not None
    assert skip_reason(get_config("granite-3-2b"),
                       SHAPES["train_4k"]) is None
    assert skip_reason(get_config("xlstm-1.3b"),
                       SHAPES["long_500k"]) is None


def test_roofline_terms_and_bottleneck():
    r = roofline_terms(197e12, 0.0, 0.0)
    assert r["bottleneck"] == "compute" and abs(r["t_compute_s"] - 1) < 1e-9
    r = roofline_terms(0.0, 819e9, 1.0)
    assert r["bottleneck"] == "memory"
    r = roofline_terms(1.0, 1.0, 50e9 * 2)
    assert r["bottleneck"] == "collective" and r["t_collective_s"] == 2.0


def test_model_flops_conventions():
    from repro.launch.dryrun import _model_flops
    cfg = get_config("granite-3-2b")
    tr = _model_flops(cfg, SHAPES["train_4k"], total=10, active=10)
    assert tr == 6.0 * 10 * 256 * 4096
    de = _model_flops(cfg, SHAPES["decode_32k"], total=10, active=7)
    assert de == 2.0 * 7 * 128  # one token per sequence, active params


def test_moe_active_params_counted():
    from repro.launch.dryrun import _abstract_params, _param_counts
    from repro.models.api import build_model
    cfg = get_config("mixtral-8x7b").reduced()
    model = build_model(cfg)
    shapes, metas = _abstract_params(model)
    total, active = _param_counts(cfg, shapes, metas)
    assert active < total  # top-2 of 4 experts in the reduced config
    frac = cfg.moe.top_k / cfg.moe.n_experts
    assert active >= total * frac * 0.5


def test_zero1_lmo_pspec_rule():
    from repro.core.muon import ParamMeta
    from repro.dist.sharding import state_pspecs
    from tests.test_sharding import FakeMesh

    class S:
        def __init__(self, shape, dtype="f"):
            self.shape = shape

    mesh = FakeMesh(data=16, model=16)
    params = {"w": S((32, 1024, 4096))}   # 32 layers: divisible by 16
    metas = {"w": ParamMeta("spectral", 1.0, 1)}
    state = {"step": S(()), "x": dict(params), "g_server": dict(params),
             "g_w": {"w": S((16, 32, 1024, 4096))}, "m_w": None,
             "cw_state": {}}
    sp = state_pspecs(state, params, metas, mesh, zero1_lmo=True)
    assert sp["x"]["w"][0] == "data"          # layer-parallel server state
    assert sp["g_w"]["w"][0] == "data"        # worker dim stays on workers
    # non-divisible stack: rule must not fire
    params2 = {"w": S((40, 1024, 4096))}
    state2 = dict(state, x=dict(params2), g_server=dict(params2),
                  g_w={"w": S((16, 40, 1024, 4096))})
    sp2 = state_pspecs(state2, params2, metas, mesh, zero1_lmo=True)
    assert sp2["x"]["w"][0] is None


def test_pad_heads_config_adaptation():
    """§Perf C2: the padded-head variant keeps head_dim and kv heads."""
    cfg = get_config("qwen2-vl-7b")
    padded = dataclasses.replace(cfg, n_heads=32, head_dim=cfg.hd)
    assert padded.hd == cfg.hd == 128
    assert padded.n_kv_heads == cfg.n_kv_heads
    assert padded.n_heads % 16 == 0


def test_make_batch_matches_input_specs(key):
    from repro.configs.base import ShapeSpec
    from repro.models.api import input_specs, make_batch
    cfg = get_config("whisper-small").reduced()
    sh = ShapeSpec("t", "train", 16, 4)
    specs = input_specs(cfg, sh, n_workers=2)
    batch = make_batch(cfg, sh, key, n_workers=2)
    assert set(specs) == set(batch)
    for k in specs:
        assert batch[k].shape == specs[k].shape
        assert batch[k].dtype == specs[k].dtype


def test_compile_cache_follows_env_else_checkout(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache is
    the fixed .jax_cache/ of the checkout."""
    import jax

    from repro.launch import compile_cache as cc

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cc.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = cc.enable_compile_cache()
        assert path == str(cc.CHECKOUT / ".jax_cache")
        assert (cc.CHECKOUT / "pyproject.toml").exists()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
