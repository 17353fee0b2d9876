"""Standalone cluster launchers:

    python -m presto_tpu.server --coordinator --port 8080 \
        --catalog tpch:sf=1 [--min-workers 2] [--secret S]
    python -m presto_tpu.server --worker --coordinator-url http://host:8080 \
        --catalog tpch:sf=1 [--node-id w1] [--secret S]

One process per chip: both roles touch their jax device, and a TPU chip
belongs to one process at a time. On a one-chip host the coordinator runs
with `--platform cpu` and the worker owns the chip (each start line names
the device its process holds). `chip_smoke.py` runs both roles in ONE
process instead.

Reference: server/PrestoServer.java:69-119 — one binary, role decided by
config (coordinator=true/false); here by flag. Workers announce to the
coordinator (airlift discovery analog) and serve the /v1/task data plane;
the coordinator serves /v1/statement + introspection and schedules
fragments. Both sides must be configured with the same catalogs (the
reference distributes etc/catalog/*.properties the same way).

Catalog specs (repeatable --catalog):
    tpch:sf=<N>           deterministic TPC-H generator connector
    tpcds:sf=<N>          deterministic TPC-DS generator connector
    parquet:dir=<path>    directory of <table>.parquet files
    orc:dir=<path>        directory of <table>.orc files
    memory:               empty in-memory connector
Optionally prefix with a name: `--catalog warehouse=parquet:dir=/data`.
"""

from __future__ import annotations

import argparse
import signal
import sys
import time


def build_catalog(specs):
    from presto_tpu.connector import Catalog

    cat = Catalog()
    if not specs:
        specs = ["tpch:sf=0.01"]
    for i, spec in enumerate(specs):
        name = None
        if "=" in spec.split(":", 1)[0]:
            name, spec = spec.split("=", 1)
        kind, _, argstr = spec.partition(":")
        args = {}
        for kv in filter(None, argstr.split(",")):
            k, _, v = kv.partition("=")
            args[k] = v
        if kind == "tpch":
            from presto_tpu.catalog.tpch import TpchConnector

            conn = TpchConnector(float(args.get("sf", 1.0)))
        elif kind == "tpcds":
            from presto_tpu.catalog.tpcds import TpcdsConnector

            conn = TpcdsConnector(float(args.get("sf", 1.0)))
        elif kind == "parquet":
            from presto_tpu.catalog.parquet import ParquetConnector

            conn = ParquetConnector(args["dir"])
        elif kind == "orc":
            from presto_tpu.catalog.orc import OrcConnector

            conn = OrcConnector(args["dir"])
        elif kind == "memory":
            from presto_tpu.catalog.memory import MemoryConnector

            conn = MemoryConnector()
        else:
            # plugin connectors: any importable module exposing
            # create_connector(**args) -> Connector (the PluginManager /
            # ConnectorFactory SPI analog — discovery by module path
            # instead of a plugin directory scan)
            import importlib

            try:
                mod = importlib.import_module(kind)
            except ImportError:
                raise SystemExit(f"unknown catalog kind: {kind}")
            factory = getattr(mod, "create_connector", None)
            if factory is None:
                raise SystemExit(
                    f"plugin module {kind} has no create_connector()")
            conn = factory(**args)
        cat.register(name or kind, conn, default=(i == 0))
    return cat


def main(argv=None):
    p = argparse.ArgumentParser(prog="python -m presto_tpu.server")
    role = p.add_mutually_exclusive_group(required=True)
    role.add_argument("--coordinator", action="store_true")
    role.add_argument("--worker", action="store_true")
    p.add_argument("--port", type=int, default=0,
                   help="listen port (0 = ephemeral, printed on start)")
    p.add_argument("--catalog", action="append", default=[],
                   help="catalog spec, repeatable (see module docstring)")
    p.add_argument("--coordinator-url", default=None,
                   help="(worker) coordinator to announce to")
    p.add_argument("--node-id", default=None, help="(worker) node id")
    p.add_argument("--min-workers", type=int, default=1)
    p.add_argument("--secret", default=None,
                   help="shared cluster secret for task endpoints")
    p.add_argument("--batch-rows", type=int, default=1 << 17)
    p.add_argument("--run-slots", type=int, default=4,
                   help="(worker) fair-executor run slots per worker")
    p.add_argument("--memory-pool-bytes", type=int, default=None)
    p.add_argument("--spill-dir", default=None)
    p.add_argument("--platform", default=None,
                   help="jax platform for THIS process (cpu, tpu). A chip "
                        "belongs to one process at a time and both roles "
                        "touch their device: on a one-chip host start the "
                        "coordinator with --platform cpu and let the "
                        "worker own the chip")
    p.add_argument("--password-file", default=None,
                   help="(coordinator) enable BASIC auth from this file "
                        "(lines: user:salt:sha256(salt||password))")
    p.add_argument("--session-properties", default=None,
                   help="(coordinator) JSON rules file of session property "
                        "defaults matched by user/source regex")
    p.add_argument("--query-event-log", default=None,
                   help="(coordinator) append query-completion events as "
                        "JSON lines to this file (EventListener analog)")
    p.add_argument("--function-plugin", action="append", default=[],
                   help="module[:attr] exposing register_functions(registry)"
                        " — loads user scalar/aggregate functions "
                        "(Plugin.getFunctions analog), repeatable")
    p.add_argument("--cluster-memory-limit-bytes", type=int, default=None,
                   help="(coordinator) cluster-wide memory ceiling for the "
                        "low-memory killer")
    p.add_argument("--tls-dir", default=None,
                   help="serve HTTPS: directory holding (or receiving a "
                        "generated self-signed) cluster-cert.pem / "
                        "cluster-key.pem; every node passes the same dir")
    p.add_argument("--access-control-rules", default=None,
                   help="(coordinator) JSON file of first-match "
                        "table/column authorization rules")
    args = p.parse_args(argv)

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    # take the device now and name it on the start line: a second process
    # asking for a chip that is already owned fails here, not mid-query
    dev = jax.devices()
    device = f"device {dev[0].platform}:{dev[0].device_kind} x{len(dev)}"

    if args.function_plugin:
        from presto_tpu.functions import registry

        for spec in args.function_plugin:
            registry().load_plugin(spec)

    catalog = build_catalog(args.catalog)

    if args.coordinator:
        from presto_tpu.exec.runtime import ExecConfig
        from presto_tpu.server.coordinator import Coordinator

        authenticator = spm = None
        tls = access_control = None
        if args.tls_dir:
            from presto_tpu.server.tls import generate_self_signed

            tls = generate_self_signed(args.tls_dir)
        if args.access_control_rules:
            from presto_tpu.server.security import AccessControl

            access_control = AccessControl(path=args.access_control_rules)
        if args.password_file:
            from presto_tpu.server.security import PasswordAuthenticator

            authenticator = PasswordAuthenticator(args.password_file)
        if args.session_properties:
            from presto_tpu.server.security import SessionPropertyManager

            spm = SessionPropertyManager(args.session_properties)
        coord = Coordinator(
            catalog, port=args.port,
            config=ExecConfig(batch_rows=args.batch_rows,
                              memory_pool_bytes=args.memory_pool_bytes,
                              spill_dir=args.spill_dir),
            min_workers=args.min_workers,
            cluster_secret=args.secret,
            authenticator=authenticator,
            session_property_manager=spm,
            query_event_log=args.query_event_log,
            cluster_memory_limit_bytes=args.cluster_memory_limit_bytes,
            access_control=access_control, tls=tls,
        )
        print(f"coordinator listening on {coord.url} ({device})", flush=True)
        stop = []
        signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
        try:
            while not stop:
                time.sleep(0.5)
        except KeyboardInterrupt:
            pass
        coord.close()
        return 0

    import os
    import socket

    from presto_tpu.server.worker import Worker

    # default id must be unique per process — the requested port is 0
    # (ephemeral) by default and NodeManager keys announcements by node_id
    node_id = args.node_id or (
        f"worker-{socket.gethostname()}-{os.getpid()}")
    wtls = None
    if args.tls_dir:
        from presto_tpu.server.tls import generate_self_signed

        wtls = generate_self_signed(args.tls_dir)
    w = Worker(
        catalog, node_id=node_id, port=args.port,
        coordinator_url=args.coordinator_url,
        memory_pool_bytes=args.memory_pool_bytes,
        spill_dir=args.spill_dir,
        cluster_secret=args.secret,
        run_slots=args.run_slots,
        tls=wtls,
    )
    print(f"worker {node_id} listening on {w.url} ({device})"
          + (f", announcing to {args.coordinator_url}"
             if args.coordinator_url else ""), flush=True)
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    try:
        while not stop and w.node_state != "shut_down":
            time.sleep(0.5)
    except KeyboardInterrupt:
        pass
    w.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
