"""Training substrate: loss decreases, checkpoint/restart bit-exactness,
resume equivalence (fault tolerance), gradient compression, ZeRO-1."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import reduce_config
from repro.configs import get_config
from repro.models import build_model
from repro.training import checkpoint as ckpt_mod
from repro.training import data as data_mod
from repro.training import optimizer as opt_mod
from repro.training import train as train_mod
from repro.training.optimizer import AdamWConfig


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_config(get_config("llama3.2-1b"))
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    data = data_mod.SyntheticLM(data_mod.DataConfig(
        vocab_size=cfg.vocab_size, seq_len=64, global_batch=4))
    return cfg, model, params, data


_STEP_FNS = {}


def _run(model, params, data, steps, n_micro=1, compress=False,
         start=0, opt_state=None):
    # memoize the jitted step per (model, n_micro, compress): every
    # fresh jax.jit(make_train_step(...)) wrapper re-traces, and the
    # compile dominated this module's wall clock
    key = (id(model), n_micro, compress)
    step_fn = _STEP_FNS.get(key)
    if step_fn is None:
        step_fn = jax.jit(train_mod.make_train_step(
            model, adamw=AdamWConfig(lr=1e-3, total_steps=100,
                                     warmup_steps=2),
            n_micro=n_micro, grad_compress=compress))
        _STEP_FNS[key] = step_fn
    opt_state = opt_mod.init_state(params) if opt_state is None \
        else opt_state
    losses = []
    for s in range(start, start + steps):
        raw = data.batch(s)
        batch = {k: jnp.asarray(v) for k, v in raw.items()}
        params, opt_state, m = step_fn(params, opt_state, batch)
        losses.append(float(m["loss"]))
    return params, opt_state, losses


def test_loss_decreases(setup):
    cfg, model, params, data = setup
    _, _, losses = _run(model, params, data, 12)
    assert losses[-1] < losses[0]


def test_microbatching_matches_full_batch(setup):
    cfg, model, params, data = setup
    p1, _, l1 = _run(model, params, data, 3, n_micro=1)
    p2, _, l2 = _run(model, params, data, 3, n_micro=2)
    # grad accumulation == full batch (up to bf16 accumulation noise)
    np.testing.assert_allclose(l1, l2, rtol=2e-2, atol=2e-2)


def test_grad_compression_close_to_exact(setup):
    cfg, model, params, data = setup
    _, _, l1 = _run(model, params, data, 5, compress=False)
    _, _, l2 = _run(model, params, data, 5, compress=True)
    np.testing.assert_allclose(l1, l2, rtol=0.1, atol=0.1)


def test_checkpoint_roundtrip_bitexact(setup, tmp_path):
    cfg, model, params, data = setup
    p1, o1, _ = _run(model, params, data, 2)
    mgr = ckpt_mod.CheckpointManager(str(tmp_path))
    mgr.save(2, (p1, o1))
    (p2, o2), manifest = mgr.restore((p1, o1))
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        assert jnp.array_equal(a, b)
    for a, b in zip(jax.tree.leaves(o1), jax.tree.leaves(o2)):
        assert jnp.array_equal(a, b)


def test_delta_checkpoint_skips_unchanged(setup, tmp_path):
    cfg, model, params, data = setup
    mgr = ckpt_mod.CheckpointManager(str(tmp_path))
    o = opt_mod.init_state(params)
    m1 = mgr.save(1, (params, o))
    m2 = mgr.save(2, (params, o))       # identical state
    assert m2["delta"]["new_bytes"] == 0
    assert m2["delta"]["reused_bytes"] > 0


def test_crash_resume_equivalence(setup, tmp_path):
    """Train 6 straight == train 3, 'crash', restore, train 3 more."""
    cfg, model, params, data = setup
    pa, oa, _ = _run(model, params, data, 6)
    p1, o1, _ = _run(model, params, data, 3)
    mgr = ckpt_mod.CheckpointManager(str(tmp_path))
    mgr.save(3, (p1, o1))
    (p1r, o1r), _ = mgr.restore((p1, o1))
    pb, ob, _ = _run(model, p1r, data, 3, start=3, opt_state=o1r)
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        assert jnp.array_equal(a, b), "resume diverged from straight run"


def test_data_pipeline_deterministic_and_seekable(setup):
    cfg, model, params, data = setup
    b1 = data.batch(7)
    b2 = data.batch(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    it = data.iterate(start_step=7)
    np.testing.assert_array_equal(next(it)["tokens"], b1["tokens"])


def test_lr_schedule_warmup_and_decay():
    cfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=100)
    lrs = [float(opt_mod.lr_schedule(cfg, jnp.asarray(s)))
           for s in (0, 5, 10, 50, 99)]
    assert lrs[0] < lrs[1] < lrs[2]
    assert lrs[2] >= lrs[3] >= lrs[4]
    assert lrs[4] >= 0.09 * cfg.lr
