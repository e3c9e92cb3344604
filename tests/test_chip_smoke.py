"""chip_smoke.py's contract off the chip, and the loud-failure rules it
leans on: on the CPU it runs both phases and fails at the device check; a
refused kernel or an unknown device is an error on any other backend; the
compile cache is placed from outside or at one fixed path."""
import json
import os
import subprocess
import sys
import warnings

import jax
import pytest

import paddle_tpu
from paddle_tpu import sysconfig
from paddle_tpu.cost_model import CHIP_SPECS, chip_spec
from paddle_tpu.ops import _fallback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def run_smoke(*args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    proc = subprocess.run([sys.executable, SMOKE, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    return proc, lines


def test_tiny_on_cpu_fails_only_at_the_device_check():
    proc, lines = run_smoke("--tiny", "--seed", "0")
    assert proc.returncode != 0, proc.stderr[-2000:]
    assert [ln.get("phase") for ln in lines[:-1]] == \
        ["device", "train", "serve"], proc.stderr[-2000:]
    last = lines[-1]
    for phase in lines[1:-1]:
        assert all(phase["gates"].values()), phase["gates"]
        assert phase["device"] == last["device"]
    assert proc.stdout.rstrip().splitlines()[-1] == json.dumps(last)
    assert last["ok"] is False and "no TPU" in last["reason"]
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}


def test_without_tiny_a_cpu_exits_at_once():
    proc, lines = run_smoke("--seed", "0")
    assert proc.returncode != 0
    assert [ln.get("phase") for ln in lines[:-1]] == ["device"]
    assert lines[-1]["ok"] is False and "no TPU" in lines[-1]["reason"]


def test_four_chip_option_runs_only_the_sharded_phase():
    proc, lines = run_smoke("--tiny", "--chips", "4", devices=4)
    assert proc.returncode != 0, proc.stderr[-2000:]
    assert [ln.get("phase") for ln in lines[:-1]] == \
        ["device", "sharded_train"], proc.stderr[-2000:]
    sharded = lines[1]
    assert all(sharded["gates"].values()), sharded["gates"]
    assert len(sharded["state_share_by_device"]) == 4
    assert lines[-1]["ok"] is False and "no TPU" in lines[-1]["reason"]
    assert lines[-1]["device"]["count"] == 4


def test_chips_must_match_the_devices_jax_sees():
    proc, lines = run_smoke("--tiny", "--chips", "4", devices=1)
    assert proc.returncode != 0
    assert "--chips 4" in lines[-1]["reason"]


def test_kernel_fallback_warns_once_on_cpu():
    _fallback._warned.clear()
    with pytest.warns(RuntimeWarning, match="falling back"):
        _fallback.kernel_fallback("smoke_kernel", ValueError("tiling"))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        _fallback.kernel_fallback("smoke_kernel", ValueError("tiling"))


@pytest.mark.parametrize("backend", ["tpu", "gpu"])
def test_kernel_fallback_raises_off_the_cpu(monkeypatch, backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(ValueError, match="tiling"):
        _fallback.kernel_fallback("smoke_kernel", ValueError("tiling"))


def test_a_refused_kernel_is_an_error_off_the_cpu(monkeypatch):
    """One of the fourteen try/except sites, end to end: a LayerNorm kernel
    that cannot be built raises instead of taking the reference path."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from paddle_tpu.ops import layer_norm

    def refuse(*a, **k):
        raise NotImplementedError("refused by the compiler")
    monkeypatch.setattr(pl, "pallas_call", refuse)
    x, w = jnp.ones((8, 128)), jnp.ones((128,))
    _fallback._warned.clear()
    with pytest.warns(RuntimeWarning):
        layer_norm._ln_fwd_impl(x, w, w)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="refused"):
        layer_norm._ln_fwd_impl(x, w, w)


def test_chip_spec_knows_its_chips_and_refuses_the_rest():
    assert chip_spec() is CHIP_SPECS["v5e"]          # the CPU default stays
    assert chip_spec("TPU v5 lite") is CHIP_SPECS["v5e"]
    with pytest.raises(ValueError, match="TPU v9"):
        chip_spec("TPU v9")


def test_chip_spec_raises_for_a_live_device_of_unknown_kind(monkeypatch):
    class Device:
        platform, device_kind = "tpu", "TPU v9"
    monkeypatch.setattr(jax, "devices", lambda *a: [Device()])
    with pytest.raises(ValueError, match="TPU v9"):
        chip_spec()


def test_set_device_tpu_needs_a_tpu():
    with pytest.raises(RuntimeError):
        paddle_tpu.set_device("tpu")
    assert paddle_tpu.set_device("cpu").platform == "cpu"


def test_compile_cache_left_alone_when_placed_from_outside(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    monkeypatch.setattr(jax.config, "update", lambda *a: pytest.fail(
        f"set {a} though JAX_COMPILATION_CACHE_DIR is set"))
    assert sysconfig.use_compile_cache() == "/somewhere/else"


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = {}
    monkeypatch.setattr(jax.config, "update", seen.__setitem__)
    path = os.path.join(ROOT, ".jax_cache")
    assert sysconfig.use_compile_cache() == path
    assert sysconfig.use_compile_cache() == path     # the same on every call
    assert seen == {"jax_compilation_cache_dir": path}


def test_a_priced_one_tick_horizon_is_still_ragged():
    """At GPT-1.3B the tick dwarfs the host sync and the horizon prices to
    K=1. That must not select the per-tick loop, whose blocking prefill
    packs a whole admission wave into one dispatch (33 GiB of temporaries
    for the smoke's eight prompts)."""
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.serving.decoder import PagedGPTDecoder
    from paddle_tpu.serving.engine import ContinuousBatchingEngine

    paddle_tpu.seed(0)
    decoder = PagedGPTDecoder(GPT(gpt_tiny()), num_pages=16, max_batch=2)
    priced = ContinuousBatchingEngine(decoder, host_sync_s=1e-12)
    assert priced.k_max == 1 and priced.ragged
    asked = ContinuousBatchingEngine(decoder, k_max=1)
    assert asked.k_max == 1 and not asked.ragged


def test_optimizer_slots_are_sharded_like_their_parameters():
    """Left to the compiler, zeros-initialised moments come out replicated
    on every device, and FSDP shards the parameters only."""
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.distributed.trainer import Trainer
    from paddle_tpu.models import GPT, gpt_tiny

    mesh = build_mesh(fsdp=2, tp=2, devices=jax.devices()[:4])
    paddle_tpu.seed(0)
    model = GPT(gpt_tiny())
    trainer = Trainer(model, paddle_tpu.optimizer.AdamW(1e-3),
                      lambda m, b: m(b["x"]).mean(), mesh=mesh)
    split = 0
    for name, slots in trainer.opt_state["slots"].items():
        for slot in slots.values():
            assert slot.sharding == trainer.params[name].sharding, name
            split += not slot.sharding.is_fully_replicated
    assert split > 0
    assert trainer.opt_state["step"].sharding.is_fully_replicated


def test_reference_gate_tells_a_wrong_stream_from_a_right_one():
    """The serve gate's measure: the model's own greedy continuation lies at
    its rows' maxima (share 0), random tokens lie about a whole
    (maximum - mean) below them."""
    import numpy as np

    sys.path.insert(0, ROOT)
    import chip_smoke

    sz = chip_smoke.sizes(tiny=True)
    model = chip_smoke.build_model(sz, seed=0)
    model.eval()
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, model.cfg.vocab_size, 16).tolist()
    greedy = []
    for _ in range(4):
        ids = np.asarray([prompt + greedy], np.int32)
        logits = np.asarray(model(paddle_tpu.to_tensor(ids))._value[0, -1],
                            np.float32)
        greedy.append(int(logits.argmax()))
    _, share = chip_smoke.reference_margins(model, [prompt], [greedy],
                                            sz["seq"])
    assert share <= chip_smoke.LOGIT_TOL
    wrong = rng.randint(0, model.cfg.vocab_size, 4).tolist()
    _, share = chip_smoke.reference_margins(model, [prompt], [wrong],
                                            sz["seq"])
    assert share > 3 * chip_smoke.LOGIT_TOL
