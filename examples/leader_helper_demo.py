"""Two-server dense PIR over real TCP sockets — the deployment model.

Thin CLI over the `serving/` runtime. The protocol, framing, batching,
deadline/retry policy, and metrics all live in
`distributed_point_functions_tpu/serving/` (`transport.py` frames proto
messages over TCP, `service.py` wraps the Leader/Helper roles from
`pir/server.py`); this script only parses flags, builds the shared demo
database, and wires the roles together:

    client ──LeaderRequest──> leader ──EncryptedHelperRequest──> helper
           <─masked response─        <──masked helper response──

The helper leg is encrypted end-to-end (client -> helper) with the
framework's X25519 + HKDF + AES-GCM hybrid scheme; the leader only ever
sees ciphertext. Responses are one-time-pad masked with the client's
AES-CTR seed, so the leader cannot read the helper's share either
(`pir/dpf_pir_server.cc:147-193` semantics).

Run it in one command (spawns helper + leader subprocesses, queries them,
checks the answers):

    python examples/leader_helper_demo.py --demo

or run the roles by hand in three terminals:

    python examples/leader_helper_demo.py --role helper --port 9001
    python examples/leader_helper_demo.py --role leader --port 9000 \
        --helper 127.0.0.1:9001
    python examples/leader_helper_demo.py --role client \
        --leader 127.0.0.1:9000 --indices 3,42,99
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

NUM_RECORDS = 512
RECORD_BYTES = 32


# ---------------------------------------------------------------------------
# Shared fixture: every role derives the same database deterministically
# (a real deployment would load it from storage).
# ---------------------------------------------------------------------------


def build_database():
    from distributed_point_functions_tpu.pir.database import (
        DenseDpfPirDatabase,
    )

    records = [
        (b"record-%04d:" % i).ljust(RECORD_BYTES, b".")
        for i in range(NUM_RECORDS)
    ]
    builder = DenseDpfPirDatabase.Builder()
    for r in records:
        builder.insert(r)
    return builder.build(), records


def _serving_config():
    """Demo-friendly knobs: no deadlines (the first request compiles jit
    programs, legitimately slow on CPU), generous helper leg."""
    from distributed_point_functions_tpu.serving import ServingConfig

    return ServingConfig(
        max_batch_size=64,
        max_wait_ms=2.0,
        request_timeout_ms=None,
        helper_timeout_ms=600_000.0,
        helper_retries=2,
    )


# ---------------------------------------------------------------------------
# Roles
# ---------------------------------------------------------------------------


def _maybe_admin(admin_port, registry, name: str, slo_config=None,
                 prober=None, bundles=None):
    """Start the operator telemetry endpoint when --admin-port is given
    (0 = auto-pick). Serves /metrics, /varz, /statusz, /tracez,
    /healthz, /eventz, and /profilez off the role's live registry,
    flight recorder, device telemetry, and event journal. `--slo-config
    <json>` attaches a declarative SLO tracker: hard breaches degrade
    /healthz to 503 and /statusz shows the burn table. With `--probe`
    (leader role) the blackbox prober and its debug bundles surface at
    /probez and /debugz, and /healthz degrades when a bit-identity
    probe goes stale."""
    if admin_port is None:
        return None
    from distributed_point_functions_tpu.observability import (
        AdminServer,
        tracing,
    )

    slo = None
    if slo_config is not None:
        from distributed_point_functions_tpu.observability.slo import (
            SloTracker,
        )

        slo = SloTracker.from_config(slo_config, registry)
    admin = AdminServer(
        registry=registry,
        recorder=tracing.default_recorder(),
        port=admin_port,
        name=name,
        slo=slo,
        prober=prober,
        bundles=bundles,
    )
    admin.start()
    extras = "".join(
        [" /probez /debugz" if prober is not None else "",
         "; SLOs: " + ",".join(o.name for o in slo.objectives)
         if slo else ""]
    )
    print(
        f"[{name}] admin endpoint on :{admin.port} "
        "(/metrics /varz /statusz /tracez /eventz /healthz /profilez"
        f"{extras})",
        flush=True,
    )
    return admin


def run_helper(port: int, admin_port=None, slo_config=None) -> None:
    from distributed_point_functions_tpu.serving import (
        FramedTcpServer,
        HelperSession,
    )
    from distributed_point_functions_tpu.testing import encrypt_decrypt

    db, _ = build_database()
    session = HelperSession(db, encrypt_decrypt.decrypt, _serving_config())
    _maybe_admin(admin_port, session.metrics, "helper", slo_config)
    server = FramedTcpServer(session.handle_wire, port=port, name="helper")
    print(f"[helper] listening on :{server.port}", flush=True)
    server.serve_forever()


def run_leader(
    port: int, helper_addr: str, admin_port=None, slo_config=None,
    probe: bool = False,
) -> None:
    from distributed_point_functions_tpu.serving import (
        FramedTcpServer,
        LeaderSession,
        TcpTransport,
        parse_hostport,
    )

    db, records = build_database()
    helper_host, helper_port = parse_hostport(helper_addr)
    session = LeaderSession(
        db, TcpTransport(helper_host, helper_port), _serving_config()
    )
    prober = bundles = None
    if probe:
        from distributed_point_functions_tpu.observability import (
            BundleManager,
        )
        from distributed_point_functions_tpu.serving.prober import Prober
        from distributed_point_functions_tpu.testing import encrypt_decrypt

        # Golden queries through the real serving path: the plain-pair
        # probes cover every planner tier locally, the e2e probe rides
        # the encrypted helper leg over the real TCP transport. A
        # bit-identity failure captures a debug bundle.
        bundles = BundleManager(name="leader")
        prober = Prober(
            session, records, encrypter=encrypt_decrypt.encrypt,
            period_s=10.0,
        )
        prober.add_failure_listener(bundles.on_probe_failure)
        prober.start()
        print(
            f"[leader] blackbox prober on ({', '.join(prober.kinds())}); "
            f"bundles -> {bundles.directory}",
            flush=True,
        )
    _maybe_admin(admin_port, session.metrics, "leader", slo_config,
                 prober=prober, bundles=bundles)
    server = FramedTcpServer(session.handle_wire, port=port, name="leader")
    print(f"[leader] listening on :{server.port}", flush=True)
    server.serve_forever()


def run_client(
    leader_addr: str, indices: list[int], max_attempts: int = 8
) -> list[bytes]:
    from distributed_point_functions_tpu import serialization
    from distributed_point_functions_tpu.observability import (
        propagation,
        tracing,
    )
    from distributed_point_functions_tpu.pir.client import DenseDpfPirClient
    from distributed_point_functions_tpu.protos import (
        private_information_retrieval_pb2 as pir_pb2,
    )
    from distributed_point_functions_tpu.serving import (
        TcpTransport,
        parse_hostport,
    )
    from distributed_point_functions_tpu.testing import encrypt_decrypt

    client = DenseDpfPirClient.create(NUM_RECORDS, encrypt_decrypt.encrypt)
    request, state = client.create_request(indices)
    inner = serialization.pir_request_to_proto(
        client.dpf, request
    ).SerializeToString()

    host, port = parse_hostport(leader_addr)
    with TcpTransport(host, port) as transport:
        # Enveloped request: carries a trace id out, and lets an
        # overloaded leader answer with a typed kind-3 refusal (the
        # RetryAfter hint) instead of a broken pipe.
        for attempt in range(max_attempts):
            data = transport.roundtrip(
                propagation.encode_request(tracing.new_trace_id(), inner)
            )
            try:
                _, payload = propagation.try_decode_response(data)
                break
            except propagation.WireErrorResponse as e:
                # Typed shed (admission quota, cost budget, brownout):
                # honor the server's backoff hint instead of hammering.
                if attempt + 1 >= max_attempts:
                    raise SystemExit(
                        f"leader still overloaded after "
                        f"{max_attempts} attempts: {e}"
                    )
                backoff = max(e.retry_after_s, 0.05)
                print(
                    f"[client] {e.error_type}: {e} — retrying in "
                    f"{backoff:.2f}s "
                    f"(attempt {attempt + 2}/{max_attempts})",
                    flush=True,
                )
                time.sleep(backoff)
    response = serialization.pir_response_from_proto(
        pir_pb2.PirResponse.FromString(payload)
    )
    return client.handle_response(response, state)


# ---------------------------------------------------------------------------
# One-command demo
# ---------------------------------------------------------------------------


def wait_listening(port: int, proc: subprocess.Popen, timeout: float = 300):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"subprocess exited early with rc={proc.returncode}"
            )
        try:
            with socket.create_connection(("localhost", port), timeout=1):
                return
        except OSError:
            time.sleep(0.5)
    raise TimeoutError(f"port {port} never came up")


def run_demo(base_port: int, platform: str) -> None:
    helper_port, leader_port = base_port + 1, base_port
    env = dict(os.environ)
    me = os.path.abspath(__file__)
    procs = [
        subprocess.Popen(
            [sys.executable, me, "--role", "helper",
             "--port", str(helper_port), "--platform", platform],
            env=env,
        ),
        subprocess.Popen(
            [sys.executable, me, "--role", "leader",
             "--port", str(leader_port),
             "--helper", f"localhost:{helper_port}",
             "--platform", platform],
            env=env,
        ),
    ]
    try:
        wait_listening(helper_port, procs[0])
        wait_listening(leader_port, procs[1])
        indices = [3, 42, NUM_RECORDS - 1]
        t0 = time.perf_counter()
        got = run_client(f"localhost:{leader_port}", indices)
        dt = time.perf_counter() - t0
        _, records = build_database()
        for idx, rec in zip(indices, got):
            status = "OK" if rec == records[idx] else "MISMATCH"
            print(f"index {idx}: {rec!r}  [{status}]")
        if [records[i] for i in indices] != got:
            raise SystemExit("demo FAILED: responses do not match records")
        print(f"demo OK: {len(indices)} private queries in {dt:.2f}s "
              "across three processes")
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", choices=["helper", "leader", "client"])
    ap.add_argument("--port", type=int, default=9000)
    ap.add_argument("--helper", default="localhost:9001",
                    help="helper host:port (leader role)")
    ap.add_argument("--leader", default="localhost:9000",
                    help="leader host:port (client role)")
    ap.add_argument("--indices", default="3,42,99")
    ap.add_argument("--admin-port", type=int, default=None,
                    help="serve the operator telemetry endpoint "
                    "(/metrics /varz /statusz /tracez /healthz /profilez) "
                    "on this port (0 = auto-pick; helper and leader roles)")
    ap.add_argument("--slo-config", default=None,
                    help="JSON file of declarative SLO objectives (see "
                    "docs/DESIGN.md §11); with --admin-port, hard "
                    "breaches degrade /healthz to 503 and /statusz "
                    "shows the burn table")
    ap.add_argument("--probe", action="store_true",
                    help="leader role: run the blackbox verification "
                    "prober (docs/DESIGN.md §15) — golden queries "
                    "through every planner tier plus the encrypted "
                    "helper leg, bit-identity asserted every cycle; "
                    "with --admin-port, history at /probez, incident "
                    "bundles at /debugz, probe staleness on /healthz")
    ap.add_argument("--demo", action="store_true",
                    help="spawn helper+leader and run a client against "
                    "them, on the CPU: the demo starts one process per "
                    "role, and a chip belongs to one process at a time, "
                    "so two roles cannot share one chip (the one-process "
                    "chip path is chip_smoke.py)")
    ap.add_argument("--platform", default="",
                    help="force a JAX platform for a role (e.g. cpu); "
                    "--demo always runs its roles on cpu")
    args = ap.parse_args()

    platform = "cpu" if args.demo else args.platform
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)

    if args.demo:
        run_demo(args.port, platform)
    elif args.role == "helper":
        run_helper(args.port, admin_port=args.admin_port,
                   slo_config=args.slo_config)
    elif args.role == "leader":
        run_leader(args.port, args.helper, admin_port=args.admin_port,
                   slo_config=args.slo_config, probe=args.probe)
    elif args.role == "client":
        indices = [int(x) for x in args.indices.split(",")]
        for i, rec in enumerate(
            run_client(args.leader, indices)
        ):
            print(f"index {indices[i]}: {rec!r}")
    else:
        raise SystemExit("pass --demo or --role")


if __name__ == "__main__":
    main()
