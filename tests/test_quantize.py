"""Quantized inference + delta-compressed weight distribution (ISSUE 8).

Covers utils/quantize.py (round-trip bounds, closed-loop delta chain
bit-exactness, base resync after a dropped delta), the WeightMailbox /
FleetRollout distribution layer (version monotonicity, late joiners), the
serving/actor agreement gate (activation AND fallback), off-mode bitwise
equality (the `device_sampling`-style default-off contract), and the
quant/publish/quant_fallback obs schema + RunHealth folding.
"""

import os

import numpy as np
import pytest

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.utils import quantize as Q

TOY = dict(
    compute_dtype="float32", frame_height=44, frame_width=44,
    history_length=2, hidden_size=32, num_cosines=8,
    num_tau_samples=4, num_tau_prime_samples=4, num_quantile_samples=4,
    quant_calib_batch=8, num_envs_per_actor=8,
)


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "dense": {"kernel": rng.normal(size=(32, 16)).astype(np.float32),
                  "bias": rng.normal(size=(16,)).astype(np.float32)},
        "conv": {"kernel": rng.normal(size=(3, 3, 4, 8)).astype(np.float32)},
        "zeros": {"kernel": np.zeros((4, 4), np.float32)},
    }


def _drift(tree, rng, scale=1e-3):
    flat = Q.flatten_tree(tree)
    return Q.unflatten_tree({
        p: a + rng.normal(scale=scale, size=a.shape).astype(np.float32)
        for p, a in flat.items()
    })


def _trees_equal(a, b) -> bool:
    fa, fb = Q.flatten_tree(a), Q.flatten_tree(b)
    return sorted(fa) == sorted(fb) and all(
        np.array_equal(fa[p], fb[p]) for p in fa)


# ------------------------------------------------------------ quantize math
class TestRoundTrip:
    def test_per_channel_error_bound(self):
        """|dequant(quant(x)) - x| <= scale/2 per channel; all-zero
        channels reconstruct exactly."""
        tree = _tree()
        dq = Q.dequantize_tree(Q.quantize_tree(tree))
        for path, leaf in Q.flatten_tree(tree).items():
            _, scale = Q.quantize_array(leaf)
            err = np.abs(Q.flatten_tree(dq)[path] - leaf)
            assert err.max() <= scale.max() / 2 + 1e-7, path
        assert np.array_equal(Q.flatten_tree(dq)["zeros/kernel"],
                              np.zeros((4, 4), np.float32))

    def test_structure_and_detection(self):
        tree = _tree()
        qt = Q.quantize_tree(tree)
        assert Q.is_quantized_tree(qt)
        assert not Q.is_quantized_tree(tree)
        for path, leaf in Q.flatten_tree(tree).items():
            assert Q.flatten_tree(qt)[f"{path}/q"].dtype == np.int8

    def test_int8_payload_is_quarter_of_fp32(self):
        tree = _tree()
        qt = Q.quantize_tree(tree)
        q_bytes = sum(a.nbytes for a in Q.flatten_tree(qt).values())
        assert q_bytes < Q.tree_bytes(tree) / 3  # int8 + small scales

    def test_agreement_helper(self):
        assert Q.greedy_agreement([1, 2, 3, 4], [1, 2, 3, 0]) == 0.75
        with pytest.raises(ValueError):
            Q.greedy_agreement([1], [1, 2])


# -------------------------------------------------------------- delta codec
class TestDeltaCodec:
    def test_chain_reconstruction_bit_exact(self):
        """A decoder applying every packet equals the encoder's closed-loop
        reconstruction BIT-exactly at every version — and equals a second
        decoder replaying the chain from base (delta-chain reconstruction
        == direct dequantize of the same stream)."""
        rng = np.random.default_rng(1)
        enc, dec = Q.DeltaEncoder(base_interval=4), Q.DeltaDecoder()
        tree = _tree()
        for v in range(1, 10):
            tree = _drift(tree, rng)
            packet = enc.encode(tree, v)
            out = dec.apply(packet)
            assert _trees_equal(out, enc.reconstructed()), v
        replayed = Q.DeltaDecoder().apply_chain(enc.chain())
        assert _trees_equal(replayed, dec.params())

    def test_base_resync_after_dropped_delta(self):
        rng = np.random.default_rng(2)
        enc, dec = Q.DeltaEncoder(base_interval=8), Q.DeltaDecoder()
        tree = _tree()
        packets = []
        for v in range(1, 6):
            tree = _drift(tree, rng)
            packets.append(enc.encode(tree, v))
        for p in packets[:3]:
            dec.apply(p)
        with pytest.raises(Q.DeltaChainBroken):
            dec.apply(packets[4])  # dropped packet 4 -> gap
        assert dec.version == 3  # the failed apply must not corrupt state
        out = dec.apply_chain(enc.chain())  # base replay resyncs
        assert dec.version == 5
        assert _trees_equal(out, enc.reconstructed())

    def test_version_monotonicity(self):
        enc = Q.DeltaEncoder()
        enc.encode(_tree(), 3)
        with pytest.raises(ValueError):
            enc.encode(_tree(), 3)
        dec = Q.DeltaDecoder()
        dec.apply_chain(enc.chain())
        with pytest.raises(ValueError):
            dec.apply(enc.chain()[0])  # duplicate packet refused

    def test_packet_save_load_round_trip(self, tmp_path):
        rng = np.random.default_rng(3)
        enc = Q.DeltaEncoder(base_interval=2)
        tree = _tree()
        for v, kind in ((1, "base"), (2, "delta")):
            tree = _drift(tree, rng)
            packet = enc.encode(tree, v)
            assert packet.kind == kind
            path = str(tmp_path / f"p{v}.npz")
            Q.save_packet(packet, path)
            loaded = Q.load_packet(path)
            assert (loaded.kind, loaded.version, loaded.base_version) == (
                packet.kind, packet.version, packet.base_version)
        # a decoder fed from DISK matches one fed in memory
        a = Q.DeltaDecoder()
        for v in (1, 2):
            a.apply(Q.load_packet(str(tmp_path / f"p{v}.npz")))
        assert _trees_equal(a.params(), enc.reconstructed())

    def test_delta_bytes_beat_fp32_3x(self):
        """The acceptance ratio at unit scale: >= 3x fewer bytes/publish
        than fp32 full, amortized over a base interval."""
        rng = np.random.default_rng(4)
        enc = Q.DeltaEncoder(base_interval=10)
        tree = _tree()
        total = 0
        n = 20
        for v in range(1, n + 1):
            tree = _drift(tree, rng, scale=1e-4)
            total += enc.encode(tree, v).nbytes()
        assert Q.tree_bytes(tree) / (total / n) >= 3.0


# ----------------------------------------------------- mailbox distribution
class TestMailboxDelta:
    def test_publish_subscribe_bit_exact_and_monotone(self, tmp_path):
        from rainbow_iqn_apex_tpu.parallel.elastic import (
            MailboxSubscriber,
            WeightMailbox,
        )

        rng = np.random.default_rng(5)
        mb = WeightMailbox(str(tmp_path / "weights.json"), base_interval=4)
        sub = MailboxSubscriber(mb)
        tree = _tree()
        for v in range(1, 10):
            tree = _drift(tree, rng)
            row = mb.publish_params(tree, v, step=v * 100)
            assert row["version"] == v and row["bytes"] > 0
            got = sub.poll()
            assert got is not None and sub.version == v
            assert _trees_equal(got, mb._encoder.reconstructed())
        assert sub.poll() is None  # no new version -> no re-read
        with pytest.raises(ValueError):
            mb.publish_params(tree, 5)  # backward publish refused

    def test_late_joiner_gets_base_plus_deltas(self, tmp_path):
        from rainbow_iqn_apex_tpu.parallel.elastic import (
            MailboxSubscriber,
            WeightMailbox,
        )

        rng = np.random.default_rng(6)
        mb = WeightMailbox(str(tmp_path / "weights.json"), base_interval=4)
        tree = _tree()
        for v in range(1, 8):
            tree = _drift(tree, rng)
            mb.publish_params(tree, v)
        late = MailboxSubscriber(mb)
        got = late.poll()
        assert got is not None and late.version == 7
        assert _trees_equal(got, mb._encoder.reconstructed())
        # stateless full reconstruction agrees too
        assert _trees_equal(mb.read_params(), mb._encoder.reconstructed())

    def test_dropped_delta_subscriber_resyncs_from_base(self, tmp_path):
        from rainbow_iqn_apex_tpu.parallel.elastic import (
            MailboxSubscriber,
            WeightMailbox,
        )

        rng = np.random.default_rng(7)
        mb = WeightMailbox(str(tmp_path / "weights.json"), base_interval=4)
        tree = _tree()
        for v in range(1, 7):  # bases at 1 and 5; chain is now [5, 6]
            tree = _drift(tree, rng)
            mb.publish_params(tree, v)
        sub = MailboxSubscriber(mb)
        # a subscriber claiming a version it holds no state for (its process
        # restarted mid-chain): the tail delta cannot apply, the chain
        # replay must resync it
        sub._decoder.version = 5
        got = sub.poll()
        assert got is not None and sub.version == 6 and sub.resyncs == 1
        assert _trees_equal(got, mb._encoder.reconstructed())

    def test_old_chain_files_pruned_on_new_base(self, tmp_path):
        from rainbow_iqn_apex_tpu.parallel.elastic import WeightMailbox

        rng = np.random.default_rng(8)
        mb = WeightMailbox(str(tmp_path / "weights.json"), base_interval=3)
        tree = _tree()
        for v in range(1, 8):  # bases at 1, 4, 7
            tree = _drift(tree, rng)
            mb.publish_params(tree, v)
        files = os.listdir(str(tmp_path / "weights_payload"))
        versions = sorted(int(f.split("_")[1][1:]) for f in files)
        assert versions == [7]  # the new base starts a fresh chain


# ------------------------------------------------------------ fleet rollout
class _FakeTransport:
    def __init__(self):
        self._v = 0

    def version(self):
        return self._v

    def set_version(self, v):
        self._v = int(v)

    def alive(self):
        return True


class _FakeEngine:
    """Duck-typed FleetEngine reusing the REAL adopt/packet methods, so the
    rollout tests exercise the production decode path without booting a
    PolicyServer per engine."""

    def __init__(self, eid):
        from rainbow_iqn_apex_tpu.serving.fleet.registry import FleetEngine

        self.engine_id = eid
        self.transport = _FakeTransport()
        self.writer = type("W", (), {"set_weight_version": lambda s, v: None})()
        self.params = None
        outer = self

        class _S:
            def load_params(self, p):
                outer.params = p

        self.server = _S()
        self.adopt = FleetEngine.adopt.__get__(self)
        self.adopt_packet = FleetEngine.adopt_packet.__get__(self)
        self.adopt_chain = FleetEngine.adopt_chain.__get__(self)
        self._packet_decoder = FleetEngine._packet_decoder.__get__(self)


class TestRolloutDelta:
    def test_compressed_fan_out_identical_and_monotone(self):
        from rainbow_iqn_apex_tpu.serving.fleet.rollout import FleetRollout

        rng = np.random.default_rng(9)
        ro = FleetRollout(compression="int8_delta", base_interval=4)
        e1, e2 = _FakeEngine(1), _FakeEngine(2)
        ro.track(e1)
        ro.track(e2)
        tree = _tree()
        for v in range(1, 7):
            tree = _drift(tree, rng)
            r = ro.publish(tree, version=v)
            assert r["bytes"] > 0 and r["bytes_fp32"] == Q.tree_bytes(tree)
        assert e1.transport.version() == e2.transport.version() == 6
        assert _trees_equal(e1.params, e2.params)
        assert _trees_equal(e1.params, ro._codec.reconstructed())
        # backward refused at the controller, fleet target unmoved
        r = ro.publish(tree, version=3)
        assert r["event"] == "refused_backward" and ro.target_version == 6
        # ... and at the engine (defence in depth)
        with pytest.raises(ValueError):
            e1.adopt_packet(ro._codec.chain()[0])

    def test_late_joiner_synced_by_chain_replay(self):
        from rainbow_iqn_apex_tpu.serving.fleet.rollout import FleetRollout

        rng = np.random.default_rng(10)
        ro = FleetRollout(compression="int8_delta", base_interval=4)
        e1 = _FakeEngine(1)
        ro.track(e1)
        tree = _tree()
        for v in range(1, 7):
            tree = _drift(tree, rng)
            ro.publish(tree, version=v)
        late = _FakeEngine(2)
        ro.track(late)
        assert not ro.converged()  # the joiner is behind
        assert ro.sync() == 1
        assert late.transport.version() == 6
        assert _trees_equal(late.params, e1.params)
        assert ro.converged()

    def test_sync_recovers_engine_whose_load_failed(self):
        """Decode-succeeded-but-load-failed must stay repairable: the
        decoder runs ahead of the served version, and sync()'s chain replay
        must still RELOAD (keying on the transport version, not on whether
        the chain advanced the decoder) — else the engine is fenced out of
        routing forever."""
        from rainbow_iqn_apex_tpu.serving.fleet.rollout import FleetRollout

        rng = np.random.default_rng(11)
        ro = FleetRollout(compression="int8_delta", base_interval=4)
        e = _FakeEngine(1)
        ro.track(e)
        tree = _tree()
        ro.publish(tree, version=1)
        assert e.transport.version() == 1

        def boom(_params):
            raise RuntimeError("dying engine mid-adopt")

        good_load = e.server.load_params
        e.server.load_params = boom
        tree = _drift(tree, rng)
        r = ro.publish(tree, version=2)  # decode advances, serve does not
        assert r["failed"] == 1 and e.transport.version() == 1
        e.server.load_params = good_load
        assert ro.sync() == 1
        assert e.transport.version() == 2
        assert _trees_equal(e.params, ro._codec.reconstructed())

    def test_off_mode_fans_out_the_same_object(self):
        """publish_compression=off is today's path bitwise: engines adopt
        the SAME params object the controller was handed."""
        from rainbow_iqn_apex_tpu.serving.fleet.rollout import FleetRollout

        ro = FleetRollout()  # compression="off"
        e = _FakeEngine(1)
        ro.track(e)
        obj = {"k": np.ones((2, 2), np.float32)}
        row = ro.publish(obj, version=1)
        assert e.params is obj
        assert row["bytes"] == row["bytes_fp32"] == Q.tree_bytes(obj)


# ------------------------------------------------- serving agreement gate
def _toy_state(num_actions=6):
    import jax

    from rainbow_iqn_apex_tpu.ops.learn import init_train_state

    return init_train_state(Config(**TOY), num_actions, jax.random.PRNGKey(0))


class TestServingGate:
    def test_gate_activates_quantized_path(self):
        from rainbow_iqn_apex_tpu.serving.engine import InferenceEngine

        events = []
        cfg = Config(**TOY, serve_quantize="int8", quant_agreement_min=0.0,
                     serve_batch_buckets="8")
        calib = np.random.default_rng(0).integers(
            0, 255, (8, 44, 44, 2), dtype=np.uint8)
        eng = InferenceEngine(
            cfg, 6, _toy_state().params, buckets=[8], calib_obs=calib,
            quant_log=lambda kind, **f: events.append((kind, f)))
        assert eng.quant_active and eng.quant_agreement is not None
        assert events and events[-1][0] == "quant"
        a, q = eng.infer(calib[:4])
        assert a.shape == (4,) and q.shape == (4, 6)

    def test_gate_fallback_trips_and_serves_fp32(self):
        """An impossible threshold forces the fallback deterministically:
        the engine must emit one reasoned quant_fallback event per failed
        gate and keep answering — with EXACTLY the fp32 policy's actions."""
        from rainbow_iqn_apex_tpu.serving.engine import InferenceEngine

        events = []
        cfg = Config(**TOY, serve_quantize="int8", quant_agreement_min=1.01,
                     serve_batch_buckets="8")
        calib = np.random.default_rng(0).integers(
            0, 255, (8, 44, 44, 2), dtype=np.uint8)
        state = _toy_state()
        eng = InferenceEngine(
            cfg, 6, state.params, buckets=[8], calib_obs=calib,
            quant_log=lambda kind, **f: events.append((kind, f)))
        assert not eng.quant_active and eng.quant_fallbacks == 1
        kinds = [k for k, _ in events]
        assert kinds == ["quant_fallback"]
        assert events[0][1]["reason"] == "agreement_below_min"
        cfg_off = Config(**TOY, serve_quantize="off", serve_batch_buckets="8")
        ref = InferenceEngine(cfg_off, 6, state.params, buckets=[8])
        a, q = eng.infer(calib)
        a0, q0 = ref.infer(calib)
        assert np.array_equal(a, a0) and np.array_equal(q, q0)

    def test_calibration_larger_than_max_bucket_is_clamped(self):
        """A calibration batch past the largest serve bucket (the RUNBOOK
        suggests 256+) must narrow to the bucket, not crash the swap."""
        from rainbow_iqn_apex_tpu.serving.engine import InferenceEngine

        cfg = Config(**TOY, serve_quantize="int8", quant_agreement_min=0.0,
                     serve_batch_buckets="8")
        calib = np.random.default_rng(0).integers(
            0, 255, (64, 44, 44, 2), dtype=np.uint8)  # >> bucket 8
        eng = InferenceEngine(cfg, 6, _toy_state().params, buckets=[8],
                              calib_obs=calib)
        assert eng.quant_active
        eng.load_params(_toy_state().params)  # the watcher-swap path too
        assert eng.quant_active

    def test_no_calibration_means_quietly_fp32(self):
        from rainbow_iqn_apex_tpu.serving.engine import InferenceEngine

        events = []
        cfg = Config(**TOY, serve_quantize="int8", serve_batch_buckets="8")
        eng = InferenceEngine(
            cfg, 6, _toy_state().params, buckets=[8],
            quant_log=lambda kind, **f: events.append(kind))
        assert not eng.quant_active and events == []  # unevaluable != failed

    def test_off_mode_engine_bitwise_equals_default(self):
        """serve_quantize=off must be byte-for-byte the seed serving path:
        an explicit-off engine and a default-config engine return identical
        actions AND q-values for the same request stream."""
        from rainbow_iqn_apex_tpu.serving.engine import InferenceEngine

        state = _toy_state()
        e_default = InferenceEngine(Config(**TOY), 6, state.params, buckets=[8])
        e_off = InferenceEngine(Config(**TOY, serve_quantize="off"), 6,
                                state.params, buckets=[8])
        obs = np.random.default_rng(1).integers(
            0, 255, (8, 44, 44, 2), dtype=np.uint8)
        for _ in range(3):  # the serving key stream must match too
            a0, q0 = e_default.infer(obs)
            a1, q1 = e_off.infer(obs)
            assert np.array_equal(a0, a1) and np.array_equal(q0, q1)

    def test_fp8_mode_guarded(self):
        from rainbow_iqn_apex_tpu.serving.engine import InferenceEngine

        if not Q.fp8_available():
            with pytest.raises(ValueError):
                Config(**TOY, serve_quantize="fp8").serve_quantize and \
                    InferenceEngine(Config(**TOY, serve_quantize="fp8"), 6,
                                    _toy_state().params, buckets=[8])
            return
        cfg = Config(**TOY, serve_quantize="fp8", quant_agreement_min=0.0,
                     serve_batch_buckets="8")
        calib = np.random.default_rng(0).integers(
            0, 255, (8, 44, 44, 2), dtype=np.uint8)
        eng = InferenceEngine(cfg, 6, _toy_state().params, buckets=[8],
                              calib_obs=calib)
        assert eng.quant_active
        a, _ = eng.infer(calib[:4])
        assert a.shape == (4,)

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError):
            Q.check_mode("int4")


# --------------------------------------------------- apex driver actor lanes
class TestApexDriverQuant:
    def test_off_mode_driver_bitwise(self):
        from rainbow_iqn_apex_tpu.parallel.apex import ApexDriver

        obs = np.random.default_rng(0).integers(
            0, 255, (8, 44, 44, 2), dtype=np.uint8)
        d_default = ApexDriver(Config(**TOY), 6, state_shape=(44, 44, 2))
        d_off = ApexDriver(Config(**TOY, serve_quantize="off"), 6,
                           state_shape=(44, 44, 2))
        a0, q0 = d_default.act(obs)
        a1, q1 = d_off.act(obs)
        assert np.array_equal(a0, a1) and np.array_equal(q0, q1)
        # ... and the publish path: re-published actor params bitwise equal
        d_default.publish_weights()
        d_off.publish_weights()
        flat0 = {p: np.asarray(x) for p, x in
                 Q.flatten_tree(d_default.actor_params).items()}
        flat1 = {p: np.asarray(x) for p, x in
                 Q.flatten_tree(d_off.actor_params).items()}
        assert sorted(flat0) == sorted(flat1)
        assert all(np.array_equal(flat0[p], flat1[p]) for p in flat0)

    def test_quant_publish_activates_and_acts(self):
        from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
        from rainbow_iqn_apex_tpu.parallel.apex import ApexDriver

        rows = []

        class _M:
            def log(self, kind, **f):
                rows.append((kind, f))

        reg = MetricRegistry()
        d = ApexDriver(Config(**TOY, serve_quantize="int8",
                              quant_agreement_min=0.0),
                       6, state_shape=(44, 44, 2))
        d.attach_obs(_M(), reg)
        obs = np.random.default_rng(0).integers(
            0, 255, (8, 44, 44, 2), dtype=np.uint8)
        assert d.wants_calibration()
        d.set_calibration(obs)
        v_before = d.weights_version
        d.publish_weights()
        assert d.weights_version == v_before + 1  # monotone under quant
        assert d._actor_quant and d.quant_agreement is not None
        a, q = d.act(obs)
        assert a.shape == (8,)
        frames = np.random.default_rng(1).integers(
            0, 255, (8, 44, 44), dtype=np.uint8)
        af, _ = d.act_frames(frames, np.zeros(8, bool))
        assert af.shape == (8,)
        kinds = [k for k, _ in rows]
        assert "quant" in kinds and "publish" in kinds
        pub = [f for k, f in rows if k == "publish"][-1]
        assert pub["mode"] == "int8"
        assert pub["bytes"] * 3 < pub["bytes_fp32"]
        assert reg.counter("publish_bytes_total", "learner").get() > 0

    def test_fallback_publishes_fp32_with_reasoned_row(self):
        from rainbow_iqn_apex_tpu.parallel.apex import ApexDriver

        rows = []

        class _M:
            def log(self, kind, **f):
                rows.append((kind, f))

        d = ApexDriver(Config(**TOY, serve_quantize="int8",
                              quant_agreement_min=1.01),
                       6, state_shape=(44, 44, 2))
        d.attach_obs(_M(), None)
        obs = np.random.default_rng(0).integers(
            0, 255, (8, 44, 44, 2), dtype=np.uint8)
        d.set_calibration(obs)
        d.publish_weights()
        assert not d._actor_quant and d.quant_fallbacks == 1
        fb = [f for k, f in rows if k == "quant_fallback"]
        assert fb and fb[0]["reason"] == "agreement_below_min"
        assert [f for k, f in rows if k == "publish"][-1]["mode"] == "bf16"
        # fallen-back acting IS the fp32 path (publish_weights re-broadcast)
        a, _ = d.act(obs)
        assert a.shape == (8,)


# --------------------------------------------------- schema + health folding
class TestObsSurface:
    def test_rows_schema_valid_and_lintable(self, tmp_path):
        from rainbow_iqn_apex_tpu.obs.schema import validate_row
        from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger
        from scripts.lint_jsonl import lint_file

        path = str(tmp_path / "metrics.jsonl")
        logger = MetricsLogger(path, run_id="quant_test", echo=False)
        r1 = logger.log("publish", version=3, bytes=1000, bytes_fp32=4000,
                        mode="int8", quant_active=True)
        r2 = logger.log("quant", event="gate", agreement=0.996,
                        threshold=0.99, mode="int8", active=True)
        r3 = logger.log("quant_fallback", reason="agreement_below_min",
                        agreement=0.42, threshold=0.99, mode="int8")
        logger.close()
        for row in (r1, r2, r3):
            assert validate_row(row) == []
        assert lint_file(path) == []

    def test_missing_required_keys_flagged(self):
        from rainbow_iqn_apex_tpu.obs.schema import validate_row

        bad = {"kind": "publish", "schema": 1, "ts": 0, "host": 0,
               "run": "r", "version": 1}  # no bytes
        assert any("bytes" in e for e in validate_row(bad))
        bad2 = {"kind": "quant_fallback", "schema": 1, "ts": 0, "host": 0,
                "run": "r"}  # no reason
        assert any("reason" in e for e in validate_row(bad2))

    def test_health_folds_fallbacks_and_bytes(self):
        from rainbow_iqn_apex_tpu.obs.health import RunHealth
        from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry

        reg = MetricRegistry()
        health = RunHealth(reg)
        health.observe_row({"kind": "publish", "bytes": 1000})
        health.observe_row({"kind": "quant", "agreement": 0.999})
        assert health.status() == "ok"  # clean quant traffic is healthy
        health.observe_row({"kind": "quant_fallback",
                            "reason": "agreement_below_min"})
        assert health.status() == "degraded"  # paying fp32 cost: visible
        row = health.tick(step=100)
        assert row["status"] == "degraded"
        assert health.status() == "ok"  # window closed, no new fallback
        assert reg.counter("quant_fallback_total", "health").get() == 1
        assert reg.counter("publish_bytes_total", "health").get() == 1000
        assert reg.gauge("quant_action_agreement", "health").get() == 0.999

    def test_obs_report_quant_section(self, tmp_path):
        from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger
        from scripts.obs_report import aggregate, load_rows, render

        path = str(tmp_path / "metrics.jsonl")
        logger = MetricsLogger(path, run_id="quant_test", echo=False)
        for v in range(1, 4):
            logger.log("publish", version=v, bytes=1000, bytes_fp32=4000,
                       mode="int8", quant_active=True)
        logger.log("quant", event="gate", agreement=0.997, threshold=0.99,
                   mode="int8", active=True)
        logger.log("quant_fallback", reason="agreement_below_min",
                   agreement=0.5, threshold=0.99, mode="int8")
        logger.close()
        rows, errors = load_rows([path])
        assert errors == []
        report = aggregate(rows)
        q = report["quant"]
        assert q["publishes"] == 3 and q["fallbacks"] == 1
        assert q["publish_bytes_total"] == 3000
        assert q["bytes_saved_frac"] == 0.75
        # the fallback is the NEWEST gate outcome: the report must show the
        # run as NOT quantized (a stale active=True is exactly what the
        # RUNBOOK triage must not read)
        assert q["active"] is False and q["last_agreement"] == 0.5
        assert "quant:" in render(report)

    def test_health_attribution_tallies_quant_rows(self, tmp_path):
        from rainbow_iqn_apex_tpu.obs.attribution import health_attribution
        from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

        path = str(tmp_path / "metrics.jsonl")
        logger = MetricsLogger(path, run_id="quant_test", echo=False)
        logger.log("quant_fallback", reason="agreement_below_min")
        logger.log("publish", version=1, bytes=10)
        logger.log("health", status="ok", step=1)
        logger.close()
        attribution = health_attribution(str(tmp_path / "*.jsonl"))
        assert attribution["quant"] == {"quant": 0, "quant_fallback": 1,
                                        "publish": 1}
