"""What decides how the served path runs: the platform picks the kernels
and interpret mode, the compile cache has one fixed home, ``--arch`` means
the published config, and the chip smoke refuses anything but a TPU."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import kernels
from repro.configs import get_arch_config, get_config, get_smoke_config
from repro.kernels.decode_attention import ops as da_ops
from repro.launch import compile_cache, serve, train
from repro.models import attention, nn

REPO = Path(__file__).resolve().parents[1]


def test_pallas_interpret_follows_backend(monkeypatch):
    assert jax.default_backend() == "cpu"
    assert kernels.pallas_interpret()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not kernels.pallas_interpret()


@pytest.mark.parametrize("backend,impl,kernel,interpret", [
    ("cpu", "auto", False, None),   # the CPU path is the jnp table-gather
    ("cpu", "pallas", True, True),  # forced onto the kernel: interpreted
    ("tpu", "auto", True, False),   # a TPU compiles the kernel, no config
])
def test_paged_decode_kernel_follows_backend(monkeypatch, backend, impl,
                                             kernel, interpret):
    cfg = get_config("rhapsody-demo").scaled(
        n_layers=1, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        attention_impl=impl)
    p = nn.split(attention.attention_init(jax.random.PRNGKey(0), cfg))[0]
    monkeypatch.setattr(kernels, "pallas_interpret",
                        lambda: backend == "cpu")
    calls = []
    real = da_ops.paged_decode_attention

    def spy(*a, interpret):
        calls.append(interpret)
        return real(*a, interpret=True)  # this process can only interpret

    monkeypatch.setattr(da_ops, "paged_decode_attention", spy)
    store = jnp.ones((2, 5, 4, 2, 8))  # [L, num_blocks, block, Hkv, D]
    attention.attention_decode_paged(
        p, jnp.ones((2, 1, 32)), store, store, 1,
        jnp.asarray([[1, 2], [3, 0]]), jnp.asarray([5, 2]),
        jnp.asarray([2, 3]), jnp.asarray([1, 2]), cfg)
    assert calls == ([interpret] if kernel else [])


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path):
    set_dirs = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: set_dirs.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert set_dirs == []


def test_compile_cache_default_is_fixed_and_ignored(monkeypatch):
    set_dirs = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: set_dirs.append((name, value)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert compile_cache.enable_compile_cache() == first
    assert set_dirs == [("jax_compilation_cache_dir", first)] * 2
    path = Path(first)
    assert path.parent == REPO
    assert f"{path.name}/" in (REPO / ".gitignore").read_text().split()


@pytest.mark.parametrize("launcher", [serve, train])
@pytest.mark.parametrize("argv,expected", [
    (["--arch", "qwen1.5-0.5b"], lambda: get_config("qwen1.5-0.5b")),
    (["--arch", "qwen1.5-0.5b", "--smoke"],
     lambda: get_smoke_config("qwen1.5-0.5b")),
    ([], lambda: get_config("rhapsody-demo")),
    (["--smoke"], lambda: get_smoke_config("rhapsody-demo")),
])
def test_arch_flag_means_published_config(launcher, argv, expected):
    args = launcher.build_parser().parse_args(argv)
    assert get_arch_config(args.arch, smoke=args.smoke) == expected()


def test_published_qwen_widths():
    cfg = get_arch_config("qwen1.5-0.5b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab, cfg.param_dtype) == (24, 1024, 16, 64, 2816, 151936,
                                            "bfloat16")


@pytest.fixture
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu(chip_smoke, capsys):
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.require_tpu()
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
