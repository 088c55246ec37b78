"""Live device-memory telemetry (``docs/observability.md``, "Device
memory & roofline"): the sampler's owner reconciliation, the serving
engine's ``memory_telemetry`` wiring, and the acceptance contract —
telemetry on/off leaves serving outputs bitwise-identical and mints
zero new executables, the ``dstpu_device_memory_*`` gauges survive a
/metrics text-format round trip, flight-recorder dumps carry
``memory_sample`` events, and every knob defaults off.

Smallest serving model in the suite (the test_serving_trace
discipline): every assertion here is about HOST bookkeeping."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.transformer import Transformer, TransformerConfig
from deepspeed_tpu.monitor.memwatch import (DeviceMemorySampler,
                                            MEMORY_SERIES,
                                            device_memory_record,
                                            tree_device_bytes)

SERVING = {"enabled": True, "num_slots": 2, "max_cache_len": 64,
           "prefill_chunk": 8, "prefill_token_budget": 16,
           "decode_block": 2}


@pytest.fixture(scope="module")
def shared_engine():
    model = Transformer(TransformerConfig(
        vocab_size=61, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=64, use_flash_attention=False, dtype="float32"))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 61, (2, 12)),
                      jnp.int32)
    params = model.init(jax.random.key(0), {"input_ids": ids})
    eng = deepspeed_tpu.init_inference(
        model, config={"dtype": "float32", "prefill_chunk_size": 8,
                       "serving": SERVING})
    eng.set_params(params)
    return eng


def _workload(rng, n=5):
    prompts = [rng.integers(1, 61, (int(p),)).astype(np.int32)
               for p in rng.integers(9, 21, (n,))]
    news = [int(x) for x in rng.integers(3, 9, (n,))]
    return prompts, news


def _fake_reader(in_use=1000, peak=1500, limit=16000):
    def read():
        return [{"device": "fake:0", "platform": "fake",
                 "bytes_in_use": in_use, "peak_bytes_in_use": peak,
                 "bytes_limit": limit, "limit_source": "runtime"}]
    return read


# --------------------------------------------------------------------- #
# Sampler unit behavior: reconciliation, cadence, watermark
# --------------------------------------------------------------------- #
def test_sampler_owner_reconciliation_and_unattributed():
    s = DeviceMemorySampler(
        interval_s=0.0, read_fn=_fake_reader(in_use=1000),
        owners_fn=lambda: {"params": 600, "kv": 150})
    sample = s.sample()
    assert sample["bytes_in_use"] == 1000
    assert sample["owned_bytes"] == 750
    assert sample["unattributed_bytes"] == 250
    assert sample["owners"] == {"params": 600, "kv": 150}
    # owners exceeding the reported total (a backend with no live
    # stats) clamp the gap at zero, never negative
    s2 = DeviceMemorySampler(interval_s=0.0, read_fn=_fake_reader(0, 0),
                             owners_fn=lambda: {"params": 999})
    assert s2.sample()["unattributed_bytes"] == 0


def test_sampler_interval_gating_and_flightrec():
    from deepspeed_tpu.inference.serving.flightrec import FlightRecorder
    fr = FlightRecorder(64)
    clock = [0.0]
    s = DeviceMemorySampler(interval_s=10.0, read_fn=_fake_reader(),
                            owners_fn=lambda: {"a": 1},
                            flightrec=fr, clock=lambda: clock[0])
    assert s.maybe_sample() is not None      # first call always samples
    assert s.maybe_sample() is None          # clock compare only
    clock[0] = 10.5
    assert s.maybe_sample() is not None
    assert s.samples == 2
    evs = [e for e in fr.snapshot()["events"]
           if e["ev"] == "memory_sample"]
    assert len(evs) == 2
    assert evs[0]["bytes_in_use"] == 1000
    assert evs[0]["owners"] == {"a": 1}
    assert s.last["peak_bytes_in_use"] == 1500


def test_tree_device_bytes_and_record_shape():
    tree = {"a": jnp.zeros((4, 8), jnp.float32),
            "b": [jnp.zeros((3,), jnp.int8), None]}
    assert tree_device_bytes(tree) == 4 * 8 * 4 + 3
    rec = device_memory_record()
    assert set(rec) == {"devices", "bytes_in_use", "peak_bytes_in_use",
                        "bytes_limit"}
    assert len(rec["devices"]) >= 1
    assert {"device", "bytes_in_use", "bytes_limit", "limit_source"} \
        <= set(rec["devices"][0])


# --------------------------------------------------------------------- #
# Acceptance: telemetry off/on — bitwise outputs, zero new executables
# --------------------------------------------------------------------- #
def test_memory_telemetry_off_on_bitwise_zero_new_execs(shared_engine,
                                                        tmp_path):
    eng = shared_engine
    rng = np.random.default_rng(11)
    prompts, news = _workload(rng)

    srv_off = eng.serve()
    assert srv_off._memwatch is None         # default off = seed engine
    assert srv_off.memory_snapshot() is None
    assert not any(k.startswith("hbm_") for k in srv_off.stats)
    n0 = len(eng._aot)
    rids = [srv_off.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, news)]
    outs_off = srv_off.drain()
    execs_off = len(eng._aot) - n0
    srv_off.close()

    srv = eng.serve(memory_telemetry=True, memory_sample_interval_s=0.0,
                    flight_recorder=True,
                    flight_recorder_dir=str(tmp_path / "fr"))
    n1 = len(eng._aot)
    rids_on = [srv.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, news)]
    outs_on = srv.drain()
    execs_on = len(eng._aot) - n1
    # the telemetry layer is host-side only: same executable count,
    # bitwise-identical outputs
    assert execs_on == execs_off, (execs_off, execs_on)
    for r_off, r_on in zip(rids, rids_on):
        np.testing.assert_array_equal(
            outs_off[r_off], outs_on[r_on],
            err_msg="memory telemetry changed serving outputs")

    # the run sampled every iteration (interval 0) into stats
    assert srv.stats["memory_samples"] > 0
    assert srv.stats["hbm_owned_bytes"] > 0
    owners = srv.memory_snapshot()["owners"]
    assert set(owners) == {"params", "page_pool", "slot_state"}
    assert owners["params"] == tree_device_bytes(eng._params)
    # flight recorder carries the trajectory + a dump round-trips it
    snap = srv.flightrec_snapshot()
    mem_evs = [e for e in snap["events"] if e["ev"] == "memory_sample"]
    assert mem_evs and "unattributed_bytes" in mem_evs[0]
    path = srv.dump_flightrec("memtest")
    with open(path) as f:
        dump = json.load(f)
    assert any(e["ev"] == "memory_sample" for e in dump["events"])
    srv.close()


# --------------------------------------------------------------------- #
# /metrics round trip for the dstpu_device_memory_* families
# --------------------------------------------------------------------- #
def test_metrics_round_trip_device_memory_gauges(shared_engine):
    import http.client
    from deepspeed_tpu.inference.serving.frontend import \
        ServingHTTPFrontend
    from tests.unit.test_serving_trace import parse_prometheus

    eng = shared_engine
    rng = np.random.default_rng(13)
    prompts, _ = _workload(rng, n=1)
    srv = eng.serve(memory_telemetry=True, memory_sample_interval_s=0.0)
    # deterministic nonzero device numbers regardless of backend: the
    # reader is injectable by design (the tier-1 CPU backend reports no
    # live stats)
    srv._memwatch._read = _fake_reader(in_use=5000, peak=7000,
                                       limit=16000)
    with ServingHTTPFrontend(srv) as fe:
        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=180)
        conn.request("POST", "/v1/generate", json.dumps(
            {"input_ids": [int(t) for t in prompts[0]],
             "max_new_tokens": 3}))
        assert conn.getresponse().status == 200
        conn.close()
        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=60)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        assert resp.status == 200
        body = resp.read().decode()
        conn.close()
    srv.close()

    types, helps, samples = parse_prometheus(body)
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    # every declared family is present as a gauge with HELP/TYPE
    for fam in MEMORY_SERIES:
        assert types.get(fam) == "gauge", (fam, types.get(fam))
        assert fam in helps
        assert by_name.get(fam), fam
    in_use = by_name["dstpu_device_memory_bytes_in_use"]
    assert in_use[0][0]["device"] == "fake:0"
    assert in_use[0][1] == 5000.0
    limit = by_name["dstpu_device_memory_limit_bytes"][0]
    assert limit[0]["source"] == "runtime" and limit[1] == 16000.0
    owned = {la["owner"]: v for la, v in
             by_name["dstpu_device_memory_owned_bytes"]}
    assert set(owned) == {"params", "page_pool", "slot_state"}
    # reconciliation holds inside one scrape: unattributed =
    # max(0, in_use - sum(owned))
    unattr = by_name["dstpu_device_memory_unattributed_bytes"][0][1]
    assert unattr == max(0.0, 5000.0 - sum(owned.values()))
    # the stats gauges carry the watermark too
    assert by_name["dstpu_serving_hbm_peak_bytes"][0][1] >= 5000.0


def test_memory_knobs_default_off():
    from deepspeed_tpu.inference.serving.config import ServingConfig
    cfg = ServingConfig()
    assert cfg.memory_telemetry is False
    assert cfg.memory_sample_interval_s == 10.0
