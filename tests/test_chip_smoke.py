"""``chip_smoke.py`` on the CPU: its phases at their own sizes with the
``.smoke()`` model, the faults its limits must catch, and its refusal to
report a result without a TPU."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_arch

ROOT = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()
CFG = get_arch("qwen3-0.6b").smoke()


# ----------------------------------------------------------------------
# planted faults
# ----------------------------------------------------------------------


def skip_layer(params, layer):
    """``params`` with ``layer``'s attention and MLP output projections
    zeroed: that layer adds nothing to the residual stream."""

    def f(path, leaf):
        name = getattr(path[-1], "key", None)
        return leaf.at[layer].set(0) if name in ("wo", "w_down") else leaf

    return jax.tree_util.tree_map_with_path(f, params)


def plant_skipped_layer(setattr, *, layer, programs=("prefill", "decode"),
                        sharded_only=False):
    """Make the serving engines' ``programs`` skip ``layer`` (only those
    of a mesh-native engine with ``sharded_only``)."""
    from repro.serve import engine

    init = engine._ModelRunner.__init__

    def faulty_init(self, *args, **kwargs):
        init(self, *args, **kwargs)
        if sharded_only and self.mesh is None:
            return
        api = self.api
        if "prefill" in programs:
            self._jit_prefill = jax.jit(lambda p, b: api.prefill(skip_layer(p, layer), b))
        if "decode" in programs:
            self._jit_decode = jax.jit(
                lambda p, c, t, q: api.decode(skip_layer(p, layer), c, t, q),
                donate_argnums=(1,),
            )

    setattr(engine._ModelRunner, "__init__", faulty_init)


def plant_half_batch_grads(setattr):
    """Make every ``Trainer`` built under a mesh take its gradients from the
    first half of each batch, as a step missing its gradient reduction over
    a 2-way data axis would, while it still reports the whole batch's
    loss."""
    from repro.dist.sharding import active_mesh
    from repro.train import trainer

    build = trainer.build_model

    def faulty_build(cfg):
        api = build(cfg)
        if active_mesh() is None:
            return api

        def loss(params, batch):
            whole, metrics = api.loss(params, batch)
            half, _ = api.loss(params, jax.tree.map(lambda x: x[: x.shape[0] // 2], batch))
            sg = jax.lax.stop_gradient
            return sg(whole) + half - sg(half), metrics

        return api._replace(loss=loss)

    setattr(trainer, "build_model", faulty_build)


FAULTS = {
    "none": lambda setattr: None,
    "skipped_layer": lambda setattr: plant_skipped_layer(
        setattr, layer=CFG.n_layers - 1, sharded_only=True),
    "half_batch_grads": plant_half_batch_grads,
}


def four_chip_main(fault, ckpt_dir):
    """``four_chip_phase`` with ``fault`` planted, on this process's first
    four devices; prints its result with the number of train-step compiles."""
    FAULTS[fault](setattr)
    step_compiles = []

    def on(event, secs, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration" and \
                fun_name == "jit(train_step)":
            step_compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on)
    out = cs.four_chip_phase(CFG, jax.devices(), ckpt_dir=Path(ckpt_dir))
    out["train_step_compiles"] = len(step_compiles)
    print(json.dumps(out))


def run_four_chip(fault, tmp_path):
    """``four_chip_main`` in a child process with four host CPU devices."""
    script = (f"import sys; sys.path.insert(0, {str(ROOT / 'tests')!r}); "
              f"import test_chip_smoke as t; t.four_chip_main({fault!r}, {str(tmp_path)!r})")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                          env=env, cwd=ROOT, timeout=600)


# ----------------------------------------------------------------------
# one device
# ----------------------------------------------------------------------


def test_serve_phase_at_smoke_size():
    cpu = jax.devices("cpu")[0]
    out = cs.serve_phase(CFG, device=cpu, ref_device=cpu)
    assert out["requests"] == cs.N_REQUESTS
    assert out["tokens_served"] == cs.N_REQUESTS * cs.MAX_NEW
    for key in ("logit_err_prefill", "logit_err_decode"):
        assert 0 < out[key] <= out["logit_tol"]
    assert out["compiles"] > 0 and out["compile_s"] > 0


def test_serve_phase_raises_past_its_tolerance(monkeypatch):
    cpu = jax.devices("cpu")[0]
    monkeypatch.setattr(cs, "LOGIT_TOL", 1e-9)
    with pytest.raises(RuntimeError, match="logit error"):
        cs.serve_phase(CFG, device=cpu, ref_device=cpu)


@pytest.mark.parametrize("programs", [("prefill",), ("decode",)])
def test_serve_phase_catches_a_skipped_layer(programs, monkeypatch):
    """A layer left out of either program alone fails the logit check."""
    cpu = jax.devices("cpu")[0]
    plant_skipped_layer(monkeypatch.setattr, layer=CFG.n_layers - 1, programs=programs)
    with pytest.raises(RuntimeError, match="logit error"):
        cs.serve_phase(CFG, device=cpu, ref_device=cpu)


def test_train_phase_at_smoke_size(tmp_path):
    out = cs.train_phase(CFG, device=jax.devices("cpu")[0], ckpt_dir=tmp_path / "ckpt")
    assert len(out["losses"]) == cs.TRAIN_STEPS
    assert not (tmp_path / "ckpt").exists()  # emptied after the run


# ----------------------------------------------------------------------
# four host devices
# ----------------------------------------------------------------------


def test_four_chip_phase_on_four_host_devices(tmp_path):
    """The --four-chips path on a 2x2 mesh of forced host devices."""
    r = run_four_chip("none", tmp_path)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert max(out["logit_err_prefill"], out["logit_err_decode"]) <= out["logit_tol"]
    assert out["max_divergence_margin"] <= out["margin_limit"]
    assert out["loss_rel_diff"] <= out["loss_tol"]
    assert len(out["losses_sharded"]) == cs.FOUR_CHIP_TRAIN_STEPS
    # one train-step program per run: the sharded Trainer places its fresh
    # state as the step returns it, so step 2 does not compile again
    assert out["train_step_compiles"] == 2


@pytest.mark.parametrize("fault, message", [
    ("skipped_layer", "sharded logits differ"),
    ("half_batch_grads", "sharded losses"),
])
def test_four_chip_phase_catches_a_sharded_fault(fault, message, tmp_path):
    r = run_four_chip(fault, tmp_path)
    assert r.returncode != 0
    assert message in r.stderr


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_a_tpu(where, tmp_path):
    """No accelerator, or no repo beside the script: non-zero exit and no
    result line."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, str(script)], capture_output=True, text=True,
                       env=env, cwd=script.parent, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
