"""Bring-up smoke: the served dense-PIR path on the chip, in one process.

    python chip_smoke.py                # one chip (what the driver runs)
    python chip_smoke.py --four-chips   # the 4-chip mesh session only

One chip: the README's headline deployment (BASELINE dense PIR) — a
`DenseDpfPirDatabase` of 2^20 records x 256 B generated from `--seed`,
served by a `LeaderSession` and a `HelperSession` joined by an
`InProcessTransport`, with the real hybrid encryption. After a warm-up
round that compiles the 128-key bucket, three rounds of 128 concurrent
client requests are decoded with `client.handle_response` and compared
byte for byte with the generated records. A second phase evaluates one
DPF key pair over the full log-domain-20 domain with uint64 values: the
shares must sum to beta at alpha and to 0 everywhere else.

`--four-chips`: a `PlainSession` over a 4x1 device mesh at 2^22 x 256 B
with 128-query batches must answer bit-identically to a single-device
`PlainSession` on the same data, with a non-None mesh plan and one
staged shard per device. The two sessions warm up concurrently: on
the chip's host the 4x1 mesh program took 366 s to compile and the
single-device 2^22 program more than 11 minutes (PR 21), so give the
call a limit of 20+ minutes. The 2x2 layout runs in the 4-virtual-device
rehearsal, `--four-chips --rehearse`.

Every line before the last is a progress report, not a metric. The last
line is `{"ok": true, "device": {...}}`, printed only when every phase
passed on a TPU. No TPU, a wrong byte, a nonzero fallback or demotion
counter, or an exception means a nonzero exit and no `ok` line.
`--rehearse` runs the phases at a tiny size on whatever JAX finds (the
CPU rehearsal); it always exits nonzero.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# Counters that read 0 unless something stood in for the device path:
# an OOM-gated planner or mesh demotion (the only demotions left), or
# the Leader answering with its own share alone.
FALLBACK_COUNTERS = ("pir.mesh_fallbacks", "pir.tier_demotions")


def log(msg: str) -> None:
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def make_records(rng, num_records: int, record_bytes: int) -> list:
    raw = rng.integers(
        0, 256, num_records * record_bytes, dtype=np.uint8
    ).tobytes()
    return [
        raw[i * record_bytes:(i + 1) * record_bytes]
        for i in range(num_records)
    ]


def check_counters(metrics=()) -> None:
    from distributed_point_functions_tpu.observability import tracing

    counts = {
        name: tracing.runtime_counters.get(name)
        for name in FALLBACK_COUNTERS
    }
    for registry in metrics:
        exported = registry.export()["counters"]
        for name in ("leader.degraded_responses", "leader.helper_failures"):
            if name in exported:
                counts[name] = exported[name]
    log(f"fallback/demotion counters: {counts}")
    bad = {k: v for k, v in counts.items() if v}
    if bad:
        raise RuntimeError(f"fallback or demotion counters moved: {bad}")


def serving_tiers() -> dict:
    from distributed_point_functions_tpu.observability import tracing
    from distributed_point_functions_tpu.pir.database import (
        inner_product_tier,
    )
    from distributed_point_functions_tpu.pir.dense_eval import (
        serving_expansion,
    )
    from distributed_point_functions_tpu.pir.dense_eval_planes import (
        level_kernel_status,
    )

    impl, bitrev = serving_expansion()
    plans = {
        k: v for k, v in tracing.runtime_counters.export().items()
        if k.startswith("pir.plan.")
    }
    return {
        "expansion": getattr(impl, "__name__", str(impl)),
        "bitrev": bitrev,
        "level_kernels": level_kernel_status(),
        "inner_product": inner_product_tier(),
        "planner": plans,
    }


def phase_served_pir(args) -> None:
    from distributed_point_functions_tpu.pir.client import DenseDpfPirClient
    from distributed_point_functions_tpu.pir.database import (
        DenseDpfPirDatabase,
    )
    from distributed_point_functions_tpu.serving import (
        HelperSession,
        LeaderSession,
        ServingConfig,
    )
    from distributed_point_functions_tpu.serving.transport import (
        InProcessTransport,
    )
    from distributed_point_functions_tpu.testing import encrypt_decrypt

    n, width, batch = 1 << args.log_records, args.record_bytes, args.batch
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    records = make_records(rng, n, width)
    database = DenseDpfPirDatabase(records)
    log(f"database: {n} records x {width} B built in "
        f"{time.perf_counter() - t0:.1f}s")

    # max_wait_ms: a round's 128 requests form one 128-key bucket, the
    # one shape the warm-up compiled.
    config = ServingConfig(max_batch_size=batch, max_wait_ms=2000.0)
    helper = HelperSession(database, encrypt_decrypt.decrypt, config)
    leader = LeaderSession(
        database, InProcessTransport(helper.handle_wire), config
    )
    client = DenseDpfPirClient.create(n, encrypt_decrypt.encrypt)
    try:
        for rnd in range(args.rounds + 1):
            indices = [int(i) for i in rng.integers(0, n, batch)]
            requests = [client.create_request([i]) for i in indices]
            answers = [None] * batch
            errors = []

            def serve(j):
                try:
                    answers[j] = leader.handle_request(requests[j][0])
                except Exception as e:  # noqa: BLE001 - reported below
                    errors.append(e)

            threads = [
                threading.Thread(target=serve, args=(j,))
                for j in range(batch)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if errors:
                raise errors[0]
            for j, idx in enumerate(indices):
                got = client.handle_response(answers[j], requests[j][1])
                if got != [records[idx]]:
                    raise RuntimeError(
                        f"round {rnd}: request {j} (record {idx}) decoded "
                        "to the wrong bytes"
                    )
            if rnd == 0:
                log(f"compile: warm-up round (database staging, kernel "
                    f"self-check, 128-key bucket, both parties) "
                    f"{wall:.1f}s wall")
            else:
                log(f"round {rnd}: {batch} concurrent requests, "
                    f"{wall * 1e3:.1f} ms wall, every record bit-correct")
        log(f"serving tiers: {json.dumps(serving_tiers())}")
        buckets = {
            name: session.metrics.export()["counters"].get(
                f"{name}.batcher.jit_bucket_compiles"
            )
            for name, session in (("leader", leader), ("helper", helper))
        }
        log(f"batcher bucket compiles: {buckets}")
        check_counters([leader.metrics, helper.metrics])
    finally:
        leader.close()
        helper.close()


def phase_full_domain(args) -> None:
    from distributed_point_functions_tpu.dpf import (
        DistributedPointFunction,
        DpfParameters,
    )
    from distributed_point_functions_tpu.value_types import IntType

    ld = args.log_domain
    rng = np.random.default_rng(args.seed + 1)
    alpha = int(rng.integers(0, 1 << ld))
    beta = int(rng.integers(1, 1 << 63))
    dpf = DistributedPointFunction.create(
        DpfParameters(log_domain_size=ld, value_type=IntType(64))
    )
    k0, k1 = dpf.generate_keys(alpha, beta)
    shares = []
    for key in (k0, k1):
        t0 = time.perf_counter()
        out = np.asarray(
            dpf.evaluate_next([], dpf.create_evaluation_context(key))
        )
        shares.append(
            out[:, 0].astype(np.uint64)
            | (out[:, 1].astype(np.uint64) << np.uint64(32))
        )
        log(f"full-domain eval (log domain {ld}, uint64): "
            f"{time.perf_counter() - t0:.1f}s incl. compile")
    total = shares[0] + shares[1]  # uint64 arithmetic wraps mod 2^64
    want = np.zeros(1 << ld, dtype=np.uint64)
    want[alpha] = beta
    if total.shape != want.shape or not np.array_equal(total, want):
        raise RuntimeError("full-domain shares do not sum to beta at alpha")
    log(f"full-domain DPF: {1 << ld} leaves, shares sum to beta at "
        f"alpha={alpha} and 0 elsewhere")


def phase_four_chips(args) -> None:
    import jax

    from distributed_point_functions_tpu.parallel import make_mesh2d
    from distributed_point_functions_tpu.pir.client import DenseDpfPirClient
    from distributed_point_functions_tpu.pir.database import (
        DenseDpfPirDatabase,
    )
    from distributed_point_functions_tpu.serving import (
        PlainSession,
        ServingConfig,
    )

    if len(jax.devices()) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found "
                           f"{len(jax.devices())}")
    n, width, batch = 1 << args.log_records, args.record_bytes, args.batch
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    records = make_records(rng, n, width)
    database = DenseDpfPirDatabase(records)
    log(f"database: {n} records x {width} B built in "
        f"{time.perf_counter() - t0:.1f}s")
    client = DenseDpfPirClient.create(n, lambda pt, ci: pt)
    config = ServingConfig(max_batch_size=batch)
    rounds = []
    for _ in range(args.rounds):
        indices = [int(i) for i in rng.integers(0, n, batch)]
        rounds.append((indices, client.create_plain_requests(indices)))

    def answer(session, label):
        out = []
        for rnd, (indices, (req0, req1)) in enumerate(rounds):
            t0 = time.perf_counter()
            shares = [
                session.handle_request(r).dpf_pir_response.masked_response
                for r in (req0, req1)
            ]
            log(f"{label} round {rnd}: 2 x {batch} keys "
                f"{(time.perf_counter() - t0) * 1e3:.1f} ms")
            out.append(shares)
        return out

    def check_mesh(shape, session, got, want):
        server = session.server
        if server._mesh_plan is None:
            raise RuntimeError(f"mesh {shape}: no mesh plan")
        export = server.mesh_export()
        shards = (export.get("staging") or {}).get("shards", [])
        devices = sorted(int(s["device"]) for s in shards)
        if devices != sorted(d.id for d in session_meshes[shape].devices.flat):
            raise RuntimeError(
                f"mesh {shape}: staged shards on devices {devices}"
            )
        if got != want:
            raise RuntimeError(
                f"mesh {shape}: answers differ from single device"
            )
        log(f"mesh {shape[0]}x{shape[1]}: plan "
            f"{json.dumps(export.get('plan'))}; {len(shards)} staged "
            "shards; bit-identical to the single-device session")

    session_meshes = {shape: make_mesh2d(*shape) for shape in args.mesh_shapes}
    single = PlainSession(database, config)
    first = args.mesh_shapes[0]
    meshed = PlainSession(database, config, mesh=session_meshes[first])
    try:
        # The single-device session and the first mesh session serve
        # concurrently, so their (minutes-long) compiles overlap; only
        # one collective program ever runs at a time.
        with ThreadPoolExecutor(max_workers=2) as pool:
            want_f = pool.submit(answer, single, "single device")
            got_f = pool.submit(answer, meshed, f"mesh {first[0]}x{first[1]}")
            want, got = want_f.result(), got_f.result()
        for (indices, _), (share0, share1) in zip(rounds, want):
            decoded = [
                (np.frombuffer(a, np.uint8) ^ np.frombuffer(b, np.uint8))
                .tobytes()
                for a, b in zip(share0, share1)
            ]
            if decoded != [records[i] for i in indices]:
                raise RuntimeError("single-device shares do not reconstruct")
        log("single device: every record bit-correct")
        check_mesh(first, meshed, got, want)
    finally:
        single.close()
        meshed.close()
    for shape in args.mesh_shapes[1:]:
        session = PlainSession(database, config, mesh=session_meshes[shape])
        try:
            got = answer(session, f"mesh {shape[0]}x{shape[1]}")
            check_mesh(shape, session, got, want)
        finally:
            session.close()
    check_counters()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device mesh session and its "
                    "single-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever JAX finds; always exits "
                    "nonzero (CPU rehearsal)")
    args = ap.parse_args()

    import jax

    from distributed_point_functions_tpu.serving.service import (
        install_compile_cache,
    )

    install_compile_cache()
    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    log(f"jax {jax.__version__}: {device}")
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearse:
        log("no TPU: nothing to smoke (use --rehearse for a CPU run)")
        return 1
    args.record_bytes, args.batch = 256, 128
    if args.rehearse:
        args.log_records, args.log_domain, args.rounds = 12, 10, 2
        args.mesh_shapes = [(4, 1), (2, 2)]
    else:
        args.log_records = 22 if args.four_chips else 20
        args.log_domain, args.rounds = 20, 3
        args.mesh_shapes = [(4, 1)]

    phases = (
        [("four-chip mesh session", phase_four_chips)]
        if args.four_chips
        else [("served PIR", phase_served_pir),
              ("full-domain DPF", phase_full_domain)]
    )
    for name, phase in phases:
        t0 = time.perf_counter()
        log(f"phase {name}: start")
        phase(args)
        log(f"phase {name}: passed in {time.perf_counter() - t0:.1f}s")
    if not on_tpu:
        log("rehearsal passed; not a TPU, so no ok line")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
