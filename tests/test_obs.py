"""Observability (ISSUE 9 contracts).

* Telemetry OFF is bitwise the pre-obs trainer: the flat ``AFadmm``
  aggregator with ``telemetry=None`` vs ``telemetry=True`` produces the
  SAME state trajectory and the same shared metric values — the obs/ keys
  are pure additions to the metrics dict, never a math change.
* Telemetry ON is scan-compatible: ``scan_rounds`` reproduces the Python
  round loop bit-for-bit with the obs/ leaves riding the scan carry.
* ``obs/`` values match hand-computed oracles: the division-free receive
  SNR formula, min-alpha reconstruction, masked per-worker tx energy, and
  active-worker counts under a deep-fade truncation scenario with faults.
* The metric-key schema is enforced in ONE place: ``merge_disjoint``
  raises on any collision between producer namespaces.
* ``MetricsSink`` JSONL: one event per round, non-finite -> null, resumed
  runs append after a resume marker, and the CI linter accepts the result.
* Layer scopes (``repro.obs.LAYERS``): ``layer`` refuses a name outside
  the vocabulary and nests as it says; the trainer's compiled step carries
  the scopes in its HLO metadata.  The launcher's ``--profile`` names each
  round's host phases in the trace, counts recompilations, counts the
  OTA kernels' grid steps and padded columns per round, and with telemetry
  on counts the rounds whose fading step redrew the channel.
"""
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import transport
from repro.core.aggregators import AFadmm
from repro.faults import FaultPlan, GuardConfig
from repro.obs import TelemetryConfig, merge_disjoint, resolve
from repro.obs.sink import MetricsSink, read_events, run_manifest
from repro.obs.validate import validate_bench, validate_run_dir

from helpers import default_cfgs, make_linreg, make_solver

KEY = jax.random.PRNGKey(0)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# config plumbing + the single disjointness assertion
# ---------------------------------------------------------------------------

def test_resolve_normalises():
    assert resolve(None) is None
    assert resolve(False) is None
    assert resolve(True) == TelemetryConfig()
    assert resolve(TelemetryConfig(per_worker=False)).per_worker is False
    assert resolve(TelemetryConfig(enabled=False)) is None
    assert obs.is_on(True) and not obs.is_on(None)
    with pytest.raises(TypeError):
        resolve("yes")


def test_merge_disjoint_rejects_collisions():
    out = merge_disjoint({"a": 1}, {"b": 2}, {"c": 3})
    assert out == {"a": 1, "b": 2, "c": 3}
    with pytest.raises(ValueError, match="key collision.*'a'"):
        merge_disjoint({"a": 1}, {"a": 2})
    with pytest.raises(ValueError, match="who-test"):
        merge_disjoint({"x": 1}, {"y": 2}, {"y": 3}, who="who-test")


# ---------------------------------------------------------------------------
# hand-computed oracles for the in-graph statistics
# ---------------------------------------------------------------------------

def test_snr_db_from_power_oracle():
    sig, npw = 400.0, 4.0
    got = float(transport.snr_db_from_power(jnp.asarray(sig),
                                            jnp.asarray(npw)))
    assert got == pytest.approx(10.0 * math.log10(sig / npw), abs=1e-5)
    # division-free guards: zero noise clamps, all-zero is the -1e3 floor
    assert float(transport.snr_db_from_power(
        jnp.asarray(1.0), jnp.asarray(0.0))) == pytest.approx(300.0)
    assert float(transport.snr_db_from_power(
        jnp.asarray(0.0), jnp.asarray(0.0))) == pytest.approx(0.0)


def test_round_telemetry_oracle():
    """``transport.round_telemetry`` against a fully hand-computed case."""
    tel = TelemetryConfig()
    y = jnp.asarray([3.0, -4.0])            # sig = 25
    noise = jnp.asarray([1.0, 1.0])         # n_eff = 2*noise -> npow = 8
    inv_alpha = jnp.asarray(2.0)            # alpha = 0.5
    energy = jnp.asarray([8.0, 12.0, 16.0])
    mask = jnp.asarray([True, False, True])
    m = transport.round_telemetry(tel, y, noise, inv_alpha, energy, mask, 3)
    assert float(m["obs/rx_snr_db"]) == pytest.approx(
        10.0 * math.log10(25.0 / 8.0), abs=1e-5)
    assert float(m["obs/min_alpha"]) == pytest.approx(0.5)
    assert float(m["obs/active_workers"]) == 2.0
    # tx_energy = energy * alpha^2, masked rows zeroed
    np.testing.assert_allclose(np.asarray(m["obs/tx_energy"]),
                               [2.0, 0.0, 4.0], rtol=1e-6)
    # nobody transmitted: inv_alpha = 0 encodes alpha = 0, not 1/0
    m0 = transport.round_telemetry(tel, y, noise, jnp.asarray(0.0),
                                   energy, None, 3)
    assert float(m0["obs/min_alpha"]) == 0.0
    assert float(m0["obs/active_workers"]) == 3.0
    # per_worker=False drops the vector leaf
    m1 = transport.round_telemetry(TelemetryConfig(per_worker=False),
                                   y, noise, inv_alpha, energy, mask, 3)
    assert "obs/tx_energy" not in m1


# ---------------------------------------------------------------------------
# transport: telemetry off is bitwise, on does not change the math
# ---------------------------------------------------------------------------

def _fused_case(W=4, d=32):
    from repro.core.channel import ChannelConfig, rayleigh
    from repro.core.cplx import Complex
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    theta = jax.random.normal(k1, (W, d))
    lam = Complex(0.3 * jax.random.normal(k2, (W, d)),
                  0.3 * jax.random.normal(k3, (W, d)))
    h = rayleigh(k4, (W, d))
    ccfg = ChannelConfig(n_workers=W, noisy=True, snr_db=20.0)
    return theta, lam, h, ccfg


@pytest.mark.parametrize("worker_chunk", [0, 2])
def test_fused_round_telemetry_is_pure_addition(worker_chunk):
    theta, lam, h, ccfg = _fused_case()
    kw = dict(backend="jnp", worker_chunk=worker_chunk)
    off = transport.ota_round_fused(theta, lam, h, KEY, 0.5, ccfg, **kw)
    on = transport.ota_round_fused(theta, lam, h, KEY, 0.5, ccfg,
                                   telemetry=True, **kw)
    assert len(off) == 3 and len(on) == 4
    for a, b in zip(off, on):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    telm = on[3]
    for k in ("obs/rx_snr_db", "obs/min_alpha", "obs/active_workers",
              "obs/tx_energy"):
        assert k in telm, k
    # SNR oracle from the round's own primitives: recompute sig/npow
    y, _sumh2, _energy, _h_air = transport.ota_round_stats(
        theta, lam, h, 0.5, backend="jnp")
    inv_alpha = on[1]
    noise = transport.matched_filter_noise_re(KEY, y.shape, ccfg)
    sig = float(np.sum(np.asarray(y) ** 2))
    npw = float(np.sum((np.asarray(noise) * float(inv_alpha)) ** 2))
    assert float(telm["obs/rx_snr_db"]) == pytest.approx(
        10.0 * math.log10(sig / npw), abs=1e-3)
    assert float(telm["obs/min_alpha"]) * float(inv_alpha) == \
        pytest.approx(1.0, rel=1e-5)


# ---------------------------------------------------------------------------
# aggregator: off == pre-obs bitwise; scan == loop with telemetry on
# ---------------------------------------------------------------------------

def _alg(W, d, telemetry=None, faulted=False, **cfg_kw):
    acfg, ccfg, plan = default_cfgs(W, d, noisy=True, snr_db=30.0,
                                    power_control=True, flip=False,
                                    **cfg_kw)
    kw = {}
    if faulted:
        kw = dict(faults=FaultPlan(crash_at=((5, 3),), nan_workers=1,
                                   burst_prob=0.3, burst_std=5.0),
                  guard=GuardConfig(policy="evict-retransmit",
                                    snr_floor_db=-60.0, max_retries=2))
    return AFadmm(acfg, ccfg, plan, telemetry=telemetry, **kw)


@pytest.mark.parametrize("faulted", [False, True])
def test_afadmm_telemetry_off_is_bitwise(faulted):
    """telemetry=None vs telemetry=True: identical state trajectory and
    identical shared metrics — obs/ keys are pure additions."""
    prob = make_linreg(KEY, W=6)
    solver = make_solver(prob, 0.5)

    def run(telemetry):
        alg = _alg(6, prob["d"], telemetry=telemetry, faulted=faulted)
        st = alg.init(KEY, prob["theta0"])
        rnd = jax.jit(lambda k, s: alg.round(k, s, solver, prob["grad_fn"]))
        ms = None
        for r in range(8):
            st, ms = rnd(jax.random.fold_in(KEY, r + 1), st)
        return st, ms

    st_off, m_off = run(None)
    st_on, m_on = run(True)
    for a, b in zip(jax.tree.leaves(st_off), jax.tree.leaves(st_on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not any(k.startswith("obs/") for k in m_off)
    for k in ("obs/rx_snr_db", "obs/min_alpha", "obs/active_workers",
              "obs/tx_energy", "obs/theta_update_norm"):
        assert k in m_on, k
    for k in m_off:
        np.testing.assert_array_equal(np.asarray(m_off[k]),
                                      np.asarray(m_on[k]), err_msg=k)


def test_afadmm_telemetry_scan_equals_loop():
    """obs/ leaves ride the scan carry bit-for-bit (incl. the (W,) vector
    leaf) — the scan-driver contract extends to telemetry."""
    prob = make_linreg(KEY, W=6)
    alg = _alg(6, prob["d"], telemetry=True, faulted=True)
    solver = make_solver(prob, alg.acfg.rho)
    st0 = alg.init(KEY, prob["theta0"])
    st_s, ms = jax.jit(lambda s: alg.scan_rounds(
        KEY, s, solver, prob["grad_fn"], 10))(st0)
    st_l = alg.init(KEY, prob["theta0"])
    rnd = jax.jit(lambda k, s: alg.round(k, s, solver, prob["grad_fn"]))
    loop_rows = []
    for r in range(10):
        st_l, m = rnd(jax.random.fold_in(KEY, r + 1), st_l)
        loop_rows.append(m)
    for a, b in zip(jax.tree.leaves(st_s), jax.tree.leaves(st_l)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ms["obs/rx_snr_db"].shape == (10,)
    assert ms["obs/tx_energy"].shape == (10, 6)
    for r in range(10):
        for k, v in loop_rows[r].items():
            np.testing.assert_array_equal(
                np.asarray(ms[k][r]), np.asarray(v), err_msg=f"{k}@{r}")


def test_faulted_round_namespaced_keys_and_guard_consistency():
    """All three producer namespaces coexist; the guard and telemetry
    report the SAME receive SNR; evicted/masked workers carry zero tx
    energy; active_workers counts the surviving transmitters."""
    prob = make_linreg(KEY, W=6)
    alg = _alg(6, prob["d"], telemetry=True, faulted=True)
    solver = make_solver(prob, alg.acfg.rho)
    st = alg.init(KEY, prob["theta0"])
    rnd = jax.jit(lambda k, s: alg.round(k, s, solver, prob["grad_fn"]))
    for r in range(8):
        st, m = rnd(jax.random.fold_in(KEY, r + 1), st)
    assert {"fault/alive", "guard/healthy", "guard/snr_db",
            "obs/rx_snr_db", "obs/tx_energy"} <= m.keys()
    np.testing.assert_array_equal(np.asarray(m["guard/snr_db"]),
                                  np.asarray(m["obs/rx_snr_db"]))
    e = np.asarray(m["obs/tx_energy"])
    alive = np.asarray(st.flt.alive)
    assert not alive[0]                    # persistent NaN worker evicted
    assert e[0] == 0.0                     # ... and transmits no energy
    assert float(m["obs/active_workers"]) <= alive.sum() + 1e-6
    assert float(m["obs/active_workers"]) == (e > 0).sum()


def test_deep_fade_participation_oracle():
    """Deep-fade truncation: obs/active_workers == W * participation (the
    scenario mask is the ONLY gate on a fault-free round)."""
    from repro.phy import make_scenario
    W = 8
    prob = make_linreg(KEY, W=W)
    acfg, ccfg, plan = default_cfgs(W, prob["d"], noisy=True, snr_db=30.0,
                                    power_control=True, flip=False)
    scn = make_scenario("deep-fade-truncation", ccfg, h_min=0.6)
    alg = AFadmm(acfg, ccfg, plan, scenario=scn, telemetry=True)
    solver = make_solver(prob, acfg.rho)
    st = alg.init(KEY, prob["theta0"])
    rnd = jax.jit(lambda k, s: alg.round(k, s, solver, prob["grad_fn"]))
    saw_truncation = False
    for r in range(12):
        st, m = rnd(jax.random.fold_in(KEY, r + 1), st)
        part = float(m["participation"])
        assert float(m["obs/active_workers"]) == pytest.approx(W * part)
        saw_truncation |= part < 1.0
    assert saw_truncation, "h_min=0.6 never truncated anyone in 12 rounds"


# ---------------------------------------------------------------------------
# history + sink
# ---------------------------------------------------------------------------

def test_history_records_vector_metrics():
    """The flat trainer's History survives (W,) vector metric leaves."""
    from repro.train import train
    prob = make_linreg(KEY, W=4)
    alg = _alg(4, prob["d"], telemetry=True)
    solver = make_solver(prob, alg.acfg.rho)
    h_s = train(alg, prob["theta0"], solver, prob["grad_fn"], 6, KEY,
                driver="scan")
    h_l = train(alg, prob["theta0"], solver, prob["grad_fn"], 6, KEY,
                driver="loop")
    for h in (h_s, h_l):
        assert len(h.extra["obs/rx_snr_db"]) == 6
        assert len(h.extra["obs/tx_energy"]) == 6
        assert all(len(row) == 4 for row in h.extra["obs/tx_energy"])
    assert h_s.extra["obs/rx_snr_db"] == h_l.extra["obs/rx_snr_db"]
    assert h_s.extra["obs/tx_energy"] == h_l.extra["obs/tx_energy"]


def test_sink_roundtrip_resume_append(tmp_path):
    rd = str(tmp_path / "run")
    with MetricsSink(rd) as sink:
        sink.write_manifest(run_manifest(test="roundtrip"))
        for r in range(3):
            sink.log_round(r, {"loss": 1.0 / (r + 1),
                               "obs/tx_energy": np.asarray([1.0, 2.0]),
                               "bad": float("nan"),
                               "_private": 7.0})
        sink.log_block(2, 0.5, 3)
    # resume: appends after a marker, manifest untouched
    man0 = json.load(open(os.path.join(rd, "manifest.json")))
    with MetricsSink(rd, resume=True) as sink:
        sink.write_manifest(run_manifest(test="CLOBBER"))
        sink.log_resume(3)
        for r in range(3, 5):
            sink.log_round(r, {"loss": 0.1})
        sink.log_done(5, 1.0)
    assert json.load(open(os.path.join(rd, "manifest.json"))) == man0
    evs = read_events(rd)
    rounds = [e["round"] for e in evs if e["event"] == "round"]
    assert rounds == [0, 1, 2, 3, 4]
    assert [e["event"] for e in evs].count("resume") == 1
    r0 = next(e for e in evs if e["event"] == "round")
    assert r0["metrics"]["bad"] is None            # non-finite -> null
    assert r0["metrics"]["obs/tx_energy"] == [1.0, 2.0]
    assert "_private" not in r0["metrics"]
    assert validate_run_dir(rd) == []


def test_sink_log_rounds_emits_every_round(tmp_path):
    rd = str(tmp_path / "run")
    with MetricsSink(rd) as sink:
        sink.write_manifest({"x": 1})
        stacked = {"loss": np.asarray([3.0, 2.0, 1.0]),
                   "obs/tx_energy": np.ones((3, 2)),
                   "_fault_aux": np.zeros((3,))}
        sink.log_rounds(10, stacked)
    evs = [e for e in read_events(rd) if e["event"] == "round"]
    assert [e["round"] for e in evs] == [10, 11, 12]
    assert evs[2]["metrics"]["loss"] == 1.0
    assert all("_fault_aux" not in e["metrics"] for e in evs)


def test_validate_catches_schema_violations(tmp_path):
    good = tmp_path / "BENCH_good.json"
    good.write_text(json.dumps({"optimised_metric": "x", "x": 1.5}))
    assert validate_bench(str(good)) == []
    bad = tmp_path / "BENCH_bad.json"
    bad.write_text(json.dumps({"optimised_metric": "nope", "x": 1.5}))
    assert validate_bench(str(bad))
    bad2 = tmp_path / "BENCH_bad2.json"
    bad2.write_text(json.dumps({"x": 1.5}))
    assert validate_bench(str(bad2))
    rd = tmp_path / "run"
    rd.mkdir()
    (rd / "manifest.json").write_text("{}")
    (rd / "metrics.jsonl").write_text(
        '{"event": "round", "round": 0, "metrics": {"loss": 1.0}}\n'
        '{"event": "party"}\n'
        '{"event": "round", "round": 1, "metrics": {"_leak": 1.0}}\n')
    errs = validate_run_dir(str(rd))
    assert any("party" in e for e in errs)
    assert any("_leak" in e for e in errs)


def test_report_summarises_runs(tmp_path, capsys):
    from repro.obs import report
    rd = str(tmp_path / "run")
    with MetricsSink(rd) as sink:
        sink.write_manifest({"arch": "toy"})
        for r in range(5):
            sink.log_round(r, {"loss": 5.0 - r, "obs/rx_snr_db": 40.0 + r,
                               "participation": 1.0})
    lines = report.summarise(rd, report.DEFAULT_KEYS)
    text = "\n".join(lines)
    assert "5 rounds" in text and "loss" in text and "obs/rx_snr_db" in text
    assert report.main([rd]) == 0
    capsys.readouterr()
    assert report.main([rd, "--csv"]) == 0
    csv = capsys.readouterr().out.strip().splitlines()
    assert len(csv) == 6                       # header + 5 rounds
    assert csv[0].startswith("run,round,loss")


# ---------------------------------------------------------------------------
# launcher end-to-end: --run-dir produces manifest + per-round JSONL +
# compile report (the scan driver logs EVERY round of each block)
# ---------------------------------------------------------------------------

def _launch(tmp, *extra):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "repro.launch.train", "--arch",
           "granite-8b", "--reduced", "--workers", "2", "--batch", "1",
           "--seq", "16", "--local-steps", "1", *extra]
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=560, cwd=REPO)


def test_launcher_run_dir_scan_logs_every_round(tmp_path):
    rd = str(tmp_path / "run")
    p = _launch(tmp_path, "--rounds", "4", "--log-every", "2",
                "--driver", "scan", "--run-dir", rd)
    assert p.returncode == 0, p.stderr[-2000:]
    assert os.path.exists(os.path.join(rd, "manifest.json"))
    assert os.path.exists(os.path.join(rd, "compile_report.json"))
    evs = read_events(rd)
    rounds = [e["round"] for e in evs if e["event"] == "round"]
    assert rounds == [0, 1, 2, 3]              # block-interior rounds kept
    assert sum(e["event"] == "block" for e in evs) == 2
    assert any(e["event"] == "done" for e in evs)
    m = evs[0]["metrics"]
    assert "obs/rx_snr_db" in m and "loss" in m
    assert validate_run_dir(rd) == []
    # stdout cadence unchanged: log_every=2 -> 2 round lines
    assert p.stdout.count("round ") == 2
    rep = json.load(open(os.path.join(rd, "compile_report.json")))
    assert rep["rounds_per_dispatch"] == 2
    man = json.load(open(os.path.join(rd, "manifest.json")))
    assert man["telemetry"] is True and man["driver"] == "scan"


def test_launcher_profile_names_host_spans(tmp_path):
    """--profile: every round is a ``round`` trace step whose host phases
    are named spans in the trace and in ``profile.json``, and the step
    compiles once (no compilation after the first dispatch)."""
    rd = str(tmp_path / "run")
    p = _launch(tmp_path, "--rounds", "3", "--log-every", "1",
                "--run-dir", rd, "--profile")
    assert p.returncode == 0, p.stderr[-2000:]
    prof = json.load(open(os.path.join(rd, "profile.json")))
    phases = {"batch", "dispatch", "readback", "log", "checkpoint"}
    assert phases | {"execute", "compile"} == set(prof["spans"])
    assert all(prof["spans"][k]["count"] == 3 for k in phases | {"execute"})
    assert prof["compiles_after_first"] == 0
    assert "compiles_after_first=0" in p.stdout
    from jax.profiler import ProfileData
    import glob
    (xplane,) = glob.glob(os.path.join(rd, "trace", "**", "*.xplane.pb"),
                          recursive=True)
    names = {e.name for plane in ProfileData.from_file(xplane).planes
             for line in plane.lines for e in line.events}
    assert phases | {"round"} <= names


def test_launcher_profile_counts_ota_grid_steps(tmp_path):
    """--profile on the packed round with the Pallas kernels: the profile
    line and ``profile.json`` give the round's grid steps, each kernel's
    cdiv(D, tile), and no padded column."""
    import math
    from repro.kernels.ota import vmem_block_cols
    from repro.models import get_model
    shapes = jax.eval_shape(get_model("granite-8b", reduced=True).init, KEY)
    d = sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))
    # the round's stats (W=2, 5 planes), demodulate (W=1, 4) and dual
    # (W=2, 8) kernels
    steps = sum(-(-d // vmem_block_cols(w, p))
                for w, p in ((2, 5), (1, 4), (2, 8)))
    rd = str(tmp_path / "run")
    p = _launch(tmp_path, "--rounds", "1", "--run-dir", rd, "--profile",
                "--backend", "pallas")
    assert p.returncode == 0, p.stderr[-2000:]
    prof = json.load(open(os.path.join(rd, "profile.json")))
    assert (prof["ota_grid_steps"], prof["ota_pad_cols"]) == (steps, 0)
    assert f"ota_grid_steps={steps}, ota_pad_cols=0" in p.stdout


@pytest.mark.parametrize("driver", ["loop", "scan"])
def test_launcher_profile_counts_chan_redraws(tmp_path, driver):
    """--profile with telemetry on: ``chan_redraws`` sums obs/chan_redraw
    over the run (4 rounds at coherence 2 redraw on rounds 2 and 4); with
    telemetry off the count is not reported."""
    common = ("--rounds", "4", "--log-every", "2", "--coherence", "2",
              "--driver", driver, "--profile")
    rd = str(tmp_path / "run")
    p = _launch(tmp_path, *common, "--run-dir", rd)
    assert p.returncode == 0, p.stderr[-2000:]
    assert json.load(open(os.path.join(rd, "profile.json")))[
        "chan_redraws"] == 2
    assert "chan_redraws=2" in p.stdout
    p = _launch(tmp_path, *common, "--telemetry", "off")
    assert p.returncode == 0, p.stderr[-2000:]
    assert "ota_grid_steps=" in p.stdout and "chan_redraws" not in p.stdout


# ---------------------------------------------------------------------------
# layer scopes: one vocabulary, opened where the work happens
# ---------------------------------------------------------------------------

def _carries(op_name, scope):
    import re
    return re.search(r"(^|[/(])" + scope + r"([)/]|$)", op_name) is not None


def _op_names(fn, *args):
    import re
    text = jax.jit(fn).lower(*args).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def test_layer_refuses_unknown_name():
    from repro.obs.profiling import layer
    with pytest.raises(ValueError, match="LAYERS"):
        layer("attention")
    assert set(obs.LAYERS) == {"chan_step", "local_steps", "ota_pack",
                               "ota_receive", "ota_dual", "penalty",
                               "ota_noise"}


def test_layer_nests_as_the_vocabulary_says():
    """A top-level scope inside another, or a nested scope outside its
    parent, opens nothing: an op carries one top-level scope at most."""
    from repro.obs.profiling import layer

    def f(x):
        with layer("ota_receive"):
            with layer("ota_pack"):          # owned by the receive
                y = jnp.sin(x)
            with layer("ota_noise"):
                y = y * jnp.cos(x)
        with layer("penalty"):               # outside local_steps
            y = jnp.tanh(y) + 1.0
        return y

    ops = _op_names(f, jnp.ones(8))
    assert any(_carries(o, "ota_noise") and _carries(o, "ota_receive")
               for o in ops)
    assert not any(_carries(o, "ota_pack") or _carries(o, "penalty")
                   for o in ops)


@pytest.mark.parametrize("mode,kw,missing", [
    ("replicated", {}, set()),
    ("sketched", {}, {"penalty"}),
    ("replicated", {"packed_uplink": False}, {"ota_pack"}),
])
def test_trainer_step_carries_layer_scopes(mode, kw, missing):
    """The reduced granite trainer's compiled step names its layers in the
    HLO metadata: every scope of the vocabulary (the sketched path has no
    penalty, the leafwise layout no packing), none nested in another
    top-level scope."""
    from repro.core.admm import AdmmConfig
    from repro.core.channel import ChannelConfig
    from repro.models import get_model
    from repro.train.llm_trainer import FLConfig, make_fl_train
    m = get_model("granite-8b", reduced=True)
    batch = {"tokens": jax.random.randint(KEY, (2, 1, 16), 0,
                                          m.cfg.vocab_size)}
    init_fn, step = make_fl_train(
        m, FLConfig(mode=mode, n_workers=2, local_steps=2, sketch_ratio=16,
                    **kw),
        AdmmConfig(rho=0.5), ChannelConfig(n_workers=2, snr_db=5.0))
    ops = _op_names(step, init_fn(KEY), batch, KEY)
    found = {s for s in obs.LAYERS if any(_carries(o, s) for o in ops)}
    assert found == set(obs.LAYERS) - missing
    top = [s for s, parent in obs.LAYERS.items() if parent is None]
    assert all(sum(_carries(o, s) for s in top) <= 1 for o in ops)


def test_span_timer_counts_compiles_after_first_dispatch():
    from repro.obs.profiling import SpanTimer
    t = SpanTimer()
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x5, x7 = np.ones(5, np.float32), np.ones(7, np.float32)
    with t.span("dispatch"):
        f(x5).block_until_ready()            # compiles: before the count
    with t.span("dispatch"):
        f(x5).block_until_ready()
    assert t.compiles_after_first == 0
    f(x7).block_until_ready()                # a new shape recompiles
    assert t.compiles_after_first == 1
    assert t.summary()["dispatch"]["count"] == 2
