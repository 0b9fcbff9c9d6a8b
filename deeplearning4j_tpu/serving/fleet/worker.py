"""fleet_worker: run one ReplicaAgent as an OS process.

The process entrypoint the cross-process fleet spawns one-per-replica
(one per chip in production)::

    python -m deeplearning4j_tpu.serving.fleet.worker \\
        --root /shared/fleet --rid 0 \\
        --builder mypkg.serving:build_engine [--warmup] [--ttl 2.0]

``--builder`` names a ``module:function`` import path; the function is
called with the replica id and must return a ready (un-started)
``GenerationEngine`` over the fleet's shared checkpoint — replicas are
HOMOGENEOUS by contract (identical params ⇒ any replica continues any
stream bit-identically), and the builder seam is how every process
constructs the same engine without pickling one across. With
``--warmup`` the engine pre-compiles every canonical serving shape
before the lease goes live, and the agent's status file advertises
``compiles_since_warm`` (pinned 0 by the kill-survivability suite: a
migrated re-prime must land in warm buckets, cross-process or not).

The agent loop then serves until a ``shutdown`` mailbox command (or
until killed — the survivable case the transport exists for).
``SIGTERM`` is the PLANNED exit: the worker drains — stops admitting,
journals progress, nacks its in-flight streams back through the ledger
(the router re-places them bit-identically on survivors), withdraws
its lease, and exits 0.

``--role prefill`` runs a ``PrefillAgent`` instead (DistServe-style
disaggregation): same builder contract, but the process serves
``prefill`` commands only, publishing KV pages to the fleet page store
and never decoding. ``--pages import|publish|full`` attaches the store
to a replica worker (import shipped pages on admission / publish
prefix inserts / both).
"""

from __future__ import annotations

import argparse
import importlib
import signal
import subprocess
import sys


def spawn(root: str, rid: int, builder: str, *, warmup: bool = False,
          ttl: float = 2.0, throttle: float = 0.0, python: str = None,
          role: str = "replica", pages: str = "off",
          **popen_kw) -> "subprocess.Popen":
    """Launch one fleet worker as a subprocess (the test/bench
    helper): ``spawn(root, 0, "mypkg.serving:build_engine")``. The
    child is a full OS process — its own interpreter, its own GIL,
    its own engine — and the ONLY thing shared with the parent is the
    fleet root. Kill it with ``proc.kill()`` (SIGKILL: the
    survivability case), ``proc.terminate()`` (SIGTERM: the planned
    drain), or mail it a ``shutdown`` command."""
    cmd = [python or sys.executable, "-m",
           "deeplearning4j_tpu.serving.fleet.worker",
           "--root", str(root), "--rid", str(int(rid)),
           "--builder", builder, "--ttl", str(float(ttl))]
    if role != "replica":
        cmd += ["--role", role]
    if pages != "off":
        cmd += ["--pages", pages]
    if throttle:
        cmd += ["--throttle", str(float(throttle))]
    if warmup:
        cmd.append("--warmup")
    return subprocess.Popen(cmd, **popen_kw)


def resolve_builder(spec: str):
    """Import ``module:function`` → the engine-builder callable."""
    mod_name, _, fn_name = spec.partition(":")
    if not mod_name or not fn_name:
        raise ValueError(
            f"--builder must be module:function, got {spec!r}")
    mod = importlib.import_module(mod_name)
    fn = getattr(mod, fn_name, None)
    if not callable(fn):
        raise ValueError(f"{spec!r} does not name a callable")
    return fn


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="fleet_worker",
        description="one serving-fleet replica agent process")
    p.add_argument("--root", required=True,
                   help="shared fleet root (leases/mail/journal/status)")
    p.add_argument("--rid", required=True, type=int,
                   help="replica id (lease rank, mailbox dir)")
    p.add_argument("--builder", required=True,
                   help="module:function returning a GenerationEngine "
                        "for a given replica id")
    p.add_argument("--ttl", type=float, default=2.0,
                   help="lease ttl seconds (death-detection horizon)")
    p.add_argument("--role", choices=("replica", "prefill"),
                   default="replica",
                   help="replica: decode-capable agent (default); "
                        "prefill: prefill-only agent publishing KV "
                        "pages to the fleet store")
    p.add_argument("--pages", choices=("off", "import", "publish",
                                       "full"), default="off",
                   help="replica page-store attachment: import shipped "
                        "pages on admission, publish prefix-cache "
                        "inserts, or both (prefill workers always "
                        "publish)")
    p.add_argument("--warmup", action="store_true",
                   help="pre-compile every serving bucket before "
                        "going live (zero retraces afterwards)")
    p.add_argument("--throttle", type=float, default=0.0,
                   help="sleep this long after each progressing "
                        "engine step (kill-mid-trace test pacing)")
    args = p.parse_args(argv)

    # import late so --help stays instant even with jax in the builder
    from deeplearning4j_tpu.util.compile_cache import (
        configure_compile_cache)
    configure_compile_cache()
    from deeplearning4j_tpu.serving.fleet.agent import ReplicaAgent
    from deeplearning4j_tpu.serving.fleet.pages import PageStore
    from deeplearning4j_tpu.serving.fleet.prefill import PrefillAgent

    builder = resolve_builder(args.builder)
    engine = builder(args.rid)
    if args.warmup:
        engine.warmup()
    if args.role == "prefill":
        store = PageStore(args.root)
        agent = PrefillAgent(engine, store, args.root, args.rid,
                             ttl=args.ttl)
        run = agent.run
    else:
        store = PageStore(args.root) if args.pages != "off" else None
        agent = ReplicaAgent(
            engine, args.root, args.rid, ttl=args.ttl,
            page_store=store,
            import_pages=args.pages in ("import", "full"),
            publish_pages=args.pages in ("publish", "full"))

        def run():
            agent.run(step_delay_s=args.throttle)
    if args.warmup:
        agent.mark_warm()
    agent.write_status()
    # SIGTERM = planned scale-in: drain (nack in-flight streams back
    # through the journal, withdraw the lease) and exit 0 — the signal
    # handler only flips a flag; the run loop does the actual work
    # outside async-signal context
    signal.signal(signal.SIGTERM,
                  lambda *_: agent.request_drain())
    try:
        run()
    except KeyboardInterrupt:
        agent.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
