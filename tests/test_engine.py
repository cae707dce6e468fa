"""Adaptive training runtime: Rungs, events, timeline, TrainSession."""
import dataclasses
import json

import jax
import pytest

from repro.configs.base import ModelConfig
from repro.core.choices import MeshChoice
from repro.core.cost import ChoiceProfile, ladder, ladder_sensitivities
from repro.engine.events import (Burst, InterferenceTrace, ScriptedFaults,
                                 ThermalTrace)
from repro.engine.rungs import Rung, default_rung_ladder, rungs_from_ladder
from repro.engine.session import TrainSession
from repro.engine.timeline import Timeline
from repro.kernels.backend import auto_attn_impl
from repro.launch.steps import init_train_state
from repro.launch.train import make_batch_fn
from repro.optim.optimizers import sgd

TINY = ModelConfig(name="engine-tiny", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                   tie_embeddings=True, source="tests/test_engine.py")


# ---------------------------------------------------------------------------
# events
# ---------------------------------------------------------------------------


def test_trace_parse_and_slowdown():
    tr = InterferenceTrace.parse("10:20:2.5, 30:35:4")
    assert tr.bursts == (Burst(10, 20, 2.5), Burst(30, 35, 4.0))
    assert tr.slowdown(9) == 1.0
    assert tr.slowdown(10) == 2.5
    assert tr.slowdown(19) == 2.5 and tr.slowdown(20) == 1.0
    assert tr.effective_slowdown(30, 0.5) == pytest.approx(2.5)
    assert tr.effective_slowdown(30, 0.0) == 1.0
    assert tr.active(12) and not tr.active(25)


@pytest.mark.parametrize("bad", ["10:5:2", "10:20:0.5", "10:20", "x:y:z"])
def test_trace_parse_rejects(bad):
    with pytest.raises((ValueError, TypeError)):
        InterferenceTrace.parse(bad)


def test_thermal_trace_parse_and_hysteresis():
    tr = ThermalTrace.parse("0.5:0.2:3.0")
    assert (tr.heat_rate, tr.cool_rate, tr.slowdown) == (0.5, 0.2, 3.0)
    tr5 = ThermalTrace.parse("0.5:0.2:3.0:2.0:1.0")
    assert (tr5.trigger_temp, tr5.release_temp) == (2.0, 1.0)

    # full power heats 0.3/step: clean until temp crosses 1.0, then throttled
    tr = ThermalTrace(heat_rate=0.5, cool_rate=0.2, slowdown=3.0,
                      trigger_temp=1.0, release_temp=0.3)
    seen = [tr.effective_slowdown(s, 1.0) for s in range(5)]
    assert seen[:3] == [1.0, 1.0, 1.0] and seen[3] == 3.0  # temp 1.2 at step 3
    # a downgraded rung (sensitivity 0.2) sheds heat, but hysteresis keeps
    # the throttle on until temp falls below release, not trigger
    slows = [tr.effective_slowdown(5 + s, 0.2) for s in range(20)]
    assert slows[0] == pytest.approx(1.4)  # still throttled, scaled by sens
    assert 1.0 in slows  # ...then released after cooling
    released = slows.index(1.0)
    assert released > 3  # cooled past trigger yet stayed throttled (hysteresis)
    assert not tr.throttled

    # re-evaluating one step (e.g. comparing candidate rungs for an
    # adaptive-vs-static curve) reads the state without advancing it
    tr2 = ThermalTrace(heat_rate=0.5, cool_rate=0.2, slowdown=3.0,
                       trigger_temp=1.0, release_temp=0.3)
    tr2.effective_slowdown(0, 1.0)
    t_after = tr2.temp
    for sens in (1.0, 0.4, 0.16):
        tr2.effective_slowdown(0, sens)
    assert tr2.temp == t_after


@pytest.mark.parametrize("bad", ["0.5:0.2", "0:0.2:3", "0.5:0.2:0.5",
                                 "0.5:0.2:3:1.0:1.5", "x:y:z"])
def test_thermal_trace_parse_rejects(bad):
    with pytest.raises((ValueError, TypeError)):
        ThermalTrace.parse(bad)


def test_scripted_faults_respect_healthy_pool():
    ev = ScriptedFaults({3: (5, 6), 7: (5,)})
    assert ev(3, [0, 1, 5, 6]) == (5, 6)
    assert ev(7, [0, 1, 6]) == ()  # 5 already dead
    assert ev(4, [0, 1]) == ()


# ---------------------------------------------------------------------------
# rungs
# ---------------------------------------------------------------------------


def test_rungs_from_mesh_choice_ladder():
    choices = [
        MeshChoice((16, 16), ("data", "model"), microbatch=1,
                   attn_impl="pallas", prime_pod=True),
        MeshChoice((8, 16), ("data", "model"), microbatch=4,
                   remat="full", prime_pod=False),
        MeshChoice((8, 8), ("data", "model"), microbatch=16,
                   prime_pod=False),
    ]
    profiles = [ChoiceProfile(choice=c, latency_s=0.1 * (i + 1), energy_j=1.0,
                              power_w=1.0, cost_key=c.cost_key())
                for i, c in enumerate(choices)]
    rungs = rungs_from_ladder(ladder(profiles))
    assert [r.mesh_shape for r in rungs] == [(16, 16), (8, 16), (8, 8)]
    assert [r.microbatch for r in rungs] == [1, 4, 16]
    assert rungs[0].attn_impl == "pallas" and rungs[1].remat == "full"
    # sensitivities decay down the ladder, latency estimates ride along
    sens = [r.interference_sensitivity for r in rungs]
    assert sens == sorted(sens, reverse=True) and sens[0] == 1.0
    assert [r.latency_estimate_s for r in rungs] == [0.1, 0.2, pytest.approx(0.3)]
    assert rungs[1].rel_latency == pytest.approx(2.0)


def test_ladder_sensitivities_shape():
    s = ladder_sensitivities(5)
    assert len(s) == 5 and s[0] == 1.0
    assert all(a >= b for a, b in zip(s, s[1:]))
    assert min(s) >= 0.1


def test_default_rung_ladder_divides_batch():
    rungs = default_rung_ladder(batch=4, microbatch=1)
    assert all(4 % r.microbatch == 0 for r in rungs)
    assert len(rungs) == 3
    only_head = default_rung_ladder(batch=3, microbatch=3)
    assert len(only_head) == 1 and only_head[0].microbatch == 3
    with pytest.raises(ValueError):
        default_rung_ladder(batch=6, microbatch=4)


def test_rung_jitted_step_is_cached():
    rung = Rung(name="r", microbatch=1, attn_impl="naive")
    opt = sgd()
    f1 = rung.jitted_step(TINY, opt, lr=0.05)
    f2 = rung.jitted_step(TINY, opt, lr=0.05)
    assert f1 is f2
    rung.invalidate()
    assert rung.jitted_step(TINY, opt, lr=0.05) is not f1


def test_rung_jitted_step_donates_state():
    """The step reuses the state's buffers: the passed-in state is consumed."""
    rung = Rung(name="r", microbatch=1, attn_impl="naive")
    opt = sgd()
    state = init_train_state(rung.build_model(TINY), opt, jax.random.PRNGKey(0))
    new_state, _ = rung.jitted_step(TINY, opt, lr=0.05)(
        state, make_batch_fn(TINY, 2, 16)(0))
    leaves = jax.tree_util.tree_leaves(state["params"])
    assert leaves and all(a.is_deleted() for a in leaves)
    assert not any(a.is_deleted()
                   for a in jax.tree_util.tree_leaves(new_state["params"]))


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_enable_compile_cache(env_dir, monkeypatch, tmp_path):
    """A set JAX_COMPILATION_CACHE_DIR wins; otherwise <checkout>/.jax_cache."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env_dir))
    try:
        compile_cache.enable_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env_dir is None:
        assert after == str(compile_cache.CACHE_DIR)
        assert (compile_cache.CACHE_DIR.parent / "chip_smoke.py").exists()
    else:
        assert after == before


# ---------------------------------------------------------------------------
# attention auto policy (kernels/backend.py capability table)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seq,interpret,expect", [
    (128, True, "naive"), (128, False, "naive"),
    (512, False, "naive"), (513, False, "pallas"),
    (1024, False, "pallas"), (1024, True, "chunked"),
    (4096, True, "chunked"),
])
def test_auto_attn_impl_policy_table(seq, interpret, expect):
    assert auto_attn_impl(seq, interpret=interpret) == expect


def test_auto_attn_impl_consults_backend():
    expect = "pallas" if jax.default_backend() == "tpu" else "chunked"
    assert auto_attn_impl(2048) == expect


# ---------------------------------------------------------------------------
# timeline
# ---------------------------------------------------------------------------


def test_timeline_summary_bottom_remesh_is_not_a_downgrade():
    tl = Timeline()
    tl.record_migration(step=3, from_rung="lean", to_rung="lean",
                        reason="device-loss", kind="remesh", cost_steps=1)
    s = tl.summary()
    assert s["n_migrations"] == 1 and s["remesh_migrations"] == 1
    assert s["downgrades"] == 0 and s["upgrades"] == 0


def test_timeline_json_roundtrip(tmp_path):
    tl = Timeline()
    tl.record_step(step=0, rung="full", latency_s=0.1, observed_s=0.1,
                   loss=2.0, warmup=True)
    tl.record_step(step=1, rung="full", latency_s=0.1, observed_s=0.3, loss=1.9)
    tl.record_migration(step=1, from_rung="full", to_rung="lean",
                        reason="interference", kind="in-place")
    p = str(tmp_path / "tl.json")
    tl.save(p)
    with open(p) as f:
        back = Timeline.from_json(json.load(f))
    assert len(back.steps) == 2 and len(back.migrations) == 1
    assert back.migrations[0].to_rung == "lean"
    assert back.summary()["downgrades"] == 1
    assert back.rung_at(1) == "full"


# ---------------------------------------------------------------------------
# the integration test: synthetic burst -> downgrade -> recover, no restart
# ---------------------------------------------------------------------------


def _ladder_with_estimates():
    rungs = default_rung_ladder(batch=8, microbatch=1, attn_impl="naive")
    for r in rungs:
        r.latency_estimate_s = 0.1 * r.rel_latency
    return rungs


def _session(rungs, trace, **kw):
    def latency_fn(step, rung, dt):
        eff = trace.effective_slowdown(step, rung.interference_sensitivity) \
            if trace else 1.0
        return rung.latency_estimate_s * eff

    return TrainSession(TINY, rungs, optimizer=sgd(), lr=0.05,
                        batch_fn=make_batch_fn(TINY, 8, 32),
                        latency_fn=latency_fn, trace=trace,
                        adaptive=True, upgrade_patience=4, verbose=False, **kw)


def test_session_burst_downgrade_recover_no_restart():
    steps, burst = 34, (8, 20, 3.0)
    trace = InterferenceTrace.parse(f"{burst[0]}:{burst[1]}:{burst[2]}")
    res = _session(_ladder_with_estimates(), trace).run(steps)
    tl = res.timeline

    # (a) downgrades to a cheaper rung within the monitor's detection window
    downs = [m for m in tl.migrations if m.reason == "interference"]
    assert downs, "no downgrade under a 3x burst"
    assert burst[0] <= downs[0].step <= burst[0] + 3, \
        f"detection too slow: {downs[0].step}"

    # (b) upgrades back after the clear-streak hysteresis
    ups = [m for m in tl.migrations if m.reason == "clear"]
    assert ups and all(m.step >= burst[1] for m in ups), \
        "upgraded before the burst cleared"
    assert res.final_rung == "full", "did not recover the fastest rung"

    # (c) never restarts: one continuous state, every step trained once
    assert len(res.losses) == steps
    assert int(res.state["step"]) == steps
    assert all(m.kind == "in-place" for m in tl.migrations)
    assert [s.step for s in tl.steps] == list(range(steps))

    # (d) final loss within tolerance of the uninterfered run
    res_clean = _session(_ladder_with_estimates(), None).run(steps)
    assert not res_clean.timeline.migrations
    assert res.losses[-1] == pytest.approx(res_clean.losses[-1], rel=0.05)
    # training still works end to end
    assert res.losses[-1] < res.losses[0]


def test_session_thermal_burst_downgrade_recover():
    """Closed-loop thermal throttling: the full rung heats the die until the
    throttle engages (the burst), the controller downgrades, the cheaper
    rung's lower power draw lets the die cool below the release threshold,
    and the clear streak upgrades back — the relinquish-and-recover dynamic
    with the event source's own hysteresis constants."""
    trace = ThermalTrace(heat_rate=0.5, cool_rate=0.3, slowdown=4.0,
                         trigger_temp=1.0, release_temp=0.4)
    res = _session(_ladder_with_estimates(), trace).run(40)
    tl = res.timeline

    downs = [m for m in tl.migrations if m.reason == "interference"]
    assert downs, "no downgrade under a 4x thermal throttle"
    # heating 0.2/step net at full power: throttle engages at step 4;
    # detection follows within the monitor's window
    assert downs[0].step >= 4, "downgraded before the throttle engaged"
    ups = [m for m in tl.migrations if m.reason == "clear"]
    assert ups, "never recovered after cooling below the release threshold"
    assert ups[0].step > downs[0].step
    assert all(m.kind == "in-place" for m in tl.migrations)
    assert len(res.losses) == 40 and int(res.state["step"]) == 40


def test_train_cli_adaptive_with_thermal_trace(tmp_path):
    from repro.launch import train as T
    out = str(tmp_path / "tl.json")
    losses = T.main(["--arch", "granite-3-2b", "--reduced", "--steps", "16",
                     "--batch", "8", "--seq", "32", "--optimizer", "adam",
                     "--lr", "1e-3", "--log-every", "100", "--adaptive",
                     "--thermal-trace", "0.6:0.3:6.0",
                     "--timeline-out", out])
    assert len(losses) == 16
    with open(out) as f:
        tl = Timeline.from_json(json.load(f))
    assert any(m.reason == "interference" for m in tl.migrations), \
        "a 6x thermal throttle must trigger at least one downgrade"


def test_train_cli_rejects_both_traces():
    from repro.launch import train as T
    with pytest.raises(SystemExit):
        T.main(["--arch", "llama3.2-1b", "--reduced", "--steps", "2",
                "--interference-trace", "1:2:2.0",
                "--thermal-trace", "0.5:0.2:2.0"])


def test_session_resume_casts_params_to_active_rung_dtype():
    import jax.numpy as jnp
    from repro.launch.steps import cast_params

    res = _session(_ladder_with_estimates(), None).run(2)
    # simulate a checkpoint written while downgraded to the bf16 rung
    stale = dict(res.state)
    stale["params"] = cast_params(res.state["params"], jnp.bfloat16)
    res2 = _session(_ladder_with_estimates(), None).run(4, start=2, state=stale)
    assert res2.final_rung == "full"
    for leaf in jax.tree_util.tree_leaves(res2.state["params"]):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.float32


def test_session_static_ignores_burst():
    trace = InterferenceTrace.parse("4:10:5.0")
    rungs = [dataclasses.replace(_ladder_with_estimates()[0], name="static")]
    res = _session(rungs, trace).run(14)
    assert not res.timeline.migrations  # single rung: nothing to migrate to
    assert {s.rung for s in res.timeline.steps} == {"static"}


def test_train_cli_adaptive_with_trace(tmp_path):
    from repro.launch import train as T
    out = str(tmp_path / "tl.json")
    losses = T.main(["--arch", "granite-3-2b", "--reduced", "--steps", "14",
                     "--batch", "8", "--seq", "32", "--optimizer", "adam",
                     "--lr", "1e-3", "--log-every", "100", "--adaptive",
                     "--interference-trace", "4:10:8.0",
                     "--timeline-out", out])
    assert len(losses) == 14
    with open(out) as f:
        tl = Timeline.from_json(json.load(f))
    assert any(m.reason == "interference" for m in tl.migrations), \
        "an 8x burst must trigger at least one downgrade"
    assert len(tl.steps) == 14


def test_train_cli_resume_past_end_exits_cleanly(tmp_path):
    from repro.launch import train as T
    ckpt = str(tmp_path / "ck")
    T.main(["--arch", "llama3.2-1b", "--reduced", "--steps", "4",
            "--batch", "2", "--seq", "16", "--ckpt-dir", ckpt,
            "--ckpt-every", "2", "--log-every", "100"])
    # resumed step (4) >= --steps (3): no IndexError, empty loss list
    losses = T.main(["--arch", "llama3.2-1b", "--reduced", "--steps", "3",
                     "--batch", "2", "--seq", "16", "--ckpt-dir", ckpt,
                     "--resume", "--log-every", "100"])
    assert losses == []
