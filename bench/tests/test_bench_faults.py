"""A run with the timed path broken underneath comes out not correct,
for each fault its cell can have (one chip: no exchange between chips).
The harness's look for a chip is skipped; the rest of a run is driven."""
import numpy as np
import pytest

from bench.tests import tiny


def test_sound_runs_are_correct():
    for name in ("lgcn-amazonbook.serve-bulk", "lgcn-steam.train"):
        result, _ = tiny.run(name)
        assert result["correct"] is True, result["checks"]


def test_answer_altered_where_produced(monkeypatch):
    from repro.serve import RecsysSession
    produce = RecsysSession.__call__

    def altered(self, user_ids):
        vals, ids = produce(self, user_ids)
        return vals, ids.at[:, -1].set((ids[:, -1] + 1) % 200)

    monkeypatch.setattr(RecsysSession, "__call__", altered)
    for name in ("lgcn-amazonbook.serve-online",
                 "lgcn-amazonbook.serve-bulk"):
        result, _ = tiny.run(name)
        assert result["correct"] is False


def test_step_returns_state_unchanged(monkeypatch):
    from repro.training.train_loop import FusedBackend
    build = FusedBackend._build_chunk

    def frozen(self, trainer, sample):
        chunk = build(self, trainer, sample)

        def unchanged(params, opt_state, seed, step_idx):
            _, _, losses = chunk(params, opt_state, seed, step_idx)
            return params, opt_state, losses
        return unchanged

    monkeypatch.setattr(FusedBackend, "_build_chunk", frozen)
    result, _ = tiny.run("lgcn-steam.train")
    assert result["correct"] is False
    assert result["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def _restart_steps(chunk, trainer):
    def restarted(params, opt_state, seed, step_idx):
        return chunk(params, opt_state, seed, step_idx - step_idx[0])
    return restarted


def _reset_optimizer(chunk, trainer):
    def reset(params, opt_state, seed, step_idx):
        return chunk(params, trainer.optimizer.init(params), seed, step_idx)
    return reset


@pytest.mark.parametrize("fault", [_restart_steps, _reset_optimizer],
                         ids=["batches_from_chunk_local_steps",
                              "optimizer_state_not_carried"])
def test_fault_after_the_first_chunk(monkeypatch, fault):
    """Faults that leave the first chunk sound show in the window's
    first chunk, which the reference follows too."""
    from repro.training.train_loop import FusedBackend
    build = FusedBackend._build_chunk

    def broken(self, trainer, sample):
        return fault(build(self, trainer, sample), trainer)

    monkeypatch.setattr(FusedBackend, "_build_chunk", broken)
    result, _ = tiny.run("lgcn-steam.train")
    assert result["correct"] is False


def test_half_the_batch_left_out(monkeypatch):
    from repro.models import lightgcn as L
    loss = L.bpr_loss_fn

    def half(params, statics, batch, cfg):
        n = batch["user"].shape[0] // 2
        return loss(params, statics, {k: v[:n] for k, v in batch.items()},
                    cfg)

    monkeypatch.setattr(L, "bpr_loss_fn", half)
    result, _ = tiny.run("lgcn-steam.train")
    assert result["correct"] is False
    assert not np.isnan(result["checks"]["loss_gap"]["value"])


@pytest.mark.parametrize("name", ["lgcn-amazonbook.serve-online",
                                  "lgcn-steam.train"])
def test_control_comes_out_not_correct(name):
    from bench import control

    result, run = tiny.run(name, seed=7)
    assert result["correct"] is True
    if run.traffic["driver"] == "serve":
        readings = control.serve_control(run.config, run, 7)
    else:
        readings = control.train_control(run.config, run.traffic, run)
        assert readings["half_batch"]["correct"] is False
    assert readings["control"]["correct"] is False
