"""Server: the long-running node process.

Mirror of the reference's pilosa.Server + server.Command assembly
(server.go:100-801, server/server.go:56-414): owns the holder, translate
store, cluster, API, and HTTP listener; Open() brings them up in the
reference's order (translate -> cluster -> holder -> monitors,
server.go:334-428) and spawns the anti-entropy / metrics loops.
"""

from __future__ import annotations

import json
import os
import threading
import time
import uuid
from typing import Optional

from .api import API
from .config import Config
from .core.holder import Holder
from .core.translate import TranslateFile
from .net import serve
from .util import (
    EventJournal,
    ExpvarStatsClient,
    NopLogger,
    NopStatsClient,
    NopTracer,
    StandardLogger,
    Tracer,
    VerboseLogger,
)


def _advertise_uri(host: str, port: int, scheme: str = "http") -> str:
    """Dialable URI for the advertised node address.  Wildcard binds
    ('', '0.0.0.0') are LISTEN addresses, not destinations — advertise
    'localhost' for them (a multi-host deployment sets an explicit
    bind host, which is advertised verbatim)."""
    if host in ("", "0.0.0.0"):
        host = "localhost"
    return f"{scheme}://{host}:{port}"


class Server:
    def __init__(self, config: Optional[Config] = None):
        self.config = config or Config()
        # Fail fast on enum-valued keys, naming the offending key: a
        # typo like `[storage] ack = "fsync"` must die HERE, not as an
        # opaque ValueError deep inside the first fragment open (or a
        # 500 to an importing client).
        from .core.fragment import ACK_LEVELS

        if self.config.storage_ack not in ACK_LEVELS:
            raise ValueError(
                f"[storage] ack = {self.config.storage_ack!r}: expected "
                f"one of {', '.join(ACK_LEVELS)}"
            )
        if self.config.cluster_replica_read not in (
            "primary", "any", "bounded"
        ):
            raise ValueError(
                f"[cluster] replica-read = "
                f"{self.config.cluster_replica_read!r}: expected "
                "primary, any, or bounded"
            )
        try:
            holddown = float(self.config.cluster_recovery_holddown_ms)
        except (TypeError, ValueError):
            holddown = -1.0
        if holddown < 0:
            raise ValueError(
                f"[cluster] recovery-holddown-ms = "
                f"{self.config.cluster_recovery_holddown_ms!r}: expected "
                "a non-negative number of milliseconds"
            )
        if int(self.config.cluster_hint_max_bytes) < 0:
            raise ValueError(
                f"[cluster] hint-max-bytes = "
                f"{self.config.cluster_hint_max_bytes!r}: expected >= 0 "
                "(0 disables hinted handoff)"
            )
        if float(self.config.cluster_hint_max_age) <= 0:
            raise ValueError(
                f"[cluster] hint-max-age = "
                f"{self.config.cluster_hint_max_age!r}: expected a "
                "positive duration"
            )
        # Fault-plane rules fail fast at construction too: a typo'd
        # chaos schedule must die HERE naming the spec, not at the
        # first intercepted request mid-drill.
        from .net import faults as faults_mod

        for spec in self.config.faults_rules:
            try:
                faults_mod.parse_rule(spec)
            except ValueError as e:
                raise ValueError(f"[faults] rules: {e}") from None
        # Observability knobs fail fast too (docs/observability.md): a
        # zero sample interval would spin the sampler loop, and an
        # error-rate target is a FRACTION of requests, not a percent.
        if self.config.obs_history and float(self.config.obs_sample_interval) <= 0:
            raise ValueError(
                f"[observability] sample-interval = "
                f"{self.config.obs_sample_interval!r}: expected a "
                "positive duration"
            )
        if self.config.obs_history and (
            float(self.config.obs_retention)
            < float(self.config.obs_sample_interval)
        ):
            raise ValueError(
                f"[observability] history-retention = "
                f"{self.config.obs_retention!r}: expected >= sample-interval"
            )
        if not 0.0 <= float(self.config.obs_slo_error_rate) <= 1.0:
            raise ValueError(
                f"[observability] slo-error-rate = "
                f"{self.config.obs_slo_error_rate!r}: expected a fraction "
                "in [0, 1] (0 disables the objective)"
            )
        if float(self.config.obs_slo_burn_threshold) < 1.0:
            raise ValueError(
                f"[observability] slo-burn-threshold = "
                f"{self.config.obs_slo_burn_threshold!r}: expected >= 1"
            )
        self.data_dir = os.path.expanduser(self.config.data_dir)
        self.logger = self._make_logger()
        self.stats = self._make_stats()
        self.tracer = self._make_tracer()
        self.holder = Holder(
            os.path.join(self.data_dir), ack=self.config.storage_ack
        )
        self.translate_store = TranslateFile(
            os.path.join(self.data_dir, ".keys")
        )
        self.cluster = None
        self.node_id = self._load_node_id()
        # Per-node structured event journal (util/events.py): gossip,
        # cluster, syncer, and engine all append to THIS node's ring —
        # served at GET /debug/events and mirrored into the log.
        self.journal = EventJournal(node=self.node_id, logger=self.logger)
        self.api: Optional[API] = None
        self.hints = None  # HintManager, wired in _setup_cluster
        self._http = None
        self._http_thread = None
        self._closing = threading.Event()
        self._monitors = []
        self._client_cache = {}

    # -- assembly ----------------------------------------------------------

    def _make_logger(self):
        if self.config.verbose:
            return VerboseLogger()
        return StandardLogger()

    def _make_stats(self):
        svc = self.config.metric_service
        if svc == "expvar":
            return ExpvarStatsClient()
        if svc == "statsd":
            try:
                from .util.statsd import StatsdClient

                return StatsdClient(self.config.metric_host)
            except Exception:
                return NopStatsClient()
        return NopStatsClient()

    def _make_tracer(self):
        t = self.config.tracing_sampler_type
        if t in ("span", "profiler"):
            # The default: always-on span tracer with the recent + slow
            # /debug/traces rings enabled out of the box.  "profiler"
            # is accepted and means the same: every stage is a profiler
            # annotation while POST /debug/pprof/trace holds a capture,
            # whatever tracer collects the spans (util/tracing.stage).
            return Tracer()
        # "none" — and any unrecognized value: an operator's typo for
        # "none" must not silently enable span retention.
        if t not in ("none", "nop", ""):
            self.logger.printf(
                "unknown tracing.sampler-type %r: tracing disabled", t
            )
        return NopTracer()

    def _load_node_id(self) -> str:
        """Stable node ID persisted to .id (server.go:409)."""
        os.makedirs(self.data_dir, exist_ok=True)
        p = os.path.join(self.data_dir, ".id")
        if os.path.exists(p):
            with open(p) as f:
                return f.read().strip()
        node_id = uuid.uuid4().hex[:16]
        with open(p, "w") as f:
            f.write(node_id)
        return node_id

    # -- lifecycle (server.go Open :334) -----------------------------------

    def open(self, port_override: Optional[int] = None):
        host, port = self.config.bind_host_port()
        if port_override is not None:
            port = port_override
        # Bind the HTTP socket FIRST (without serving): cluster, gossip,
        # and the persisted topology all capture the advertised URI
        # below, so an ephemeral port (port=0, the test-harness pattern)
        # must be resolved to the real bound port before any of them
        # run, or peers/restarts would dial ":0".
        from .net.server import bind_http, make_server_ssl_context

        ssl_ctx = None
        if self.config.tls_certificate:
            # HTTPS serving + https-scheme advertisement
            # (server/config.go:25-33; server/server.go:204-214).
            ssl_ctx = make_server_ssl_context(
                self.config.tls_certificate, self.config.tls_key
            )
        self._ssl_ctx = ssl_ctx
        self._http = bind_http(
            host if host not in ("", "0.0.0.0") else "0.0.0.0", port,
            ssl_context=ssl_ctx,
            **self._server_opts(),
        )
        port = self._http.server_address[1]
        try:
            return self._open_bound(host, port)
        except Exception:
            # Release the bound-but-never-served socket, or a retry on
            # the same port gets EADDRINUSE (close() must not shutdown()
            # a socket whose serve_forever never ran — deadlock).
            self._http.server_close()
            self._http = None
            raise

    def _open_bound(self, host: str, port: int):
        # The harness (and CLI flags) may override node_id after
        # construction; re-stamp the journal's node label before any
        # component starts appending.
        self.journal.node = self.node_id
        # jax.distributed must come up before ANY device touch (holder
        # open may place fragments) — the analogue of setupNetworking
        # preceding holder.Open (server/server.go:302-331, server.go:334).
        if self.config.jax_coordinator:
            from .parallel import multihost

            multihost.initialize(
                coordinator_address=self.config.jax_coordinator,
                num_processes=self.config.jax_num_processes or None,
                process_id=(
                    self.config.jax_process_id
                    if self.config.jax_num_processes
                    else None
                ),
            )
            self.logger.printf(
                "jax.distributed up: process %d/%d",
                multihost.process_index(),
                multihost.process_count(),
            )
        self.translate_store.open()
        self._setup_cluster(host, port)
        self._setup_faults(host, port)
        # Parallel snapshot re-open (warm-start, docs/durability.md):
        # fragment decode is numpy-heavy and releases the GIL, so a
        # restart with a big holder comes up in parallel workers.
        self.holder.open(workers=self.config.storage_open_workers)
        if self.cluster is not None:
            self.cluster.holder = self.holder
        mesh_engine = self._make_mesh_engine()
        if self.cluster is not None:
            local_node = self.cluster.node
        else:
            # Single-node (no cluster config): /status must still report
            # the REAL node id + bound address, not a placeholder.
            from .cluster import Node

            local_node = Node(
                self.node_id, _advertise_uri(host, port, self.scheme), True
            )
        self.api = API(
            holder=self.holder,
            translate_store=self.translate_store,
            cluster=self.cluster,
            node=local_node,
            stats=self.stats,
            tracer=self.tracer,
            mesh_engine=mesh_engine,
            long_query_time=self.config.cluster_long_query_time,
            logger=self.logger,
            journal=self.journal,
        )
        # The readiness probe's gossip-convergence check reads the
        # transport directly (None when gossip is not configured).
        self.api.gossip = getattr(self, "gossip", None)
        self.api.mesh_required = self.config.mesh_devices >= 0
        if mesh_engine is not None and self.config.mesh_sequencer:
            mesh_engine.ticket = self._make_ticket_fn()
        self._http, self._http_thread = serve(
            self.api,
            srv=self._http,
            allowed_origins=self.config.handler_allowed_origins,
            admission=self._make_admission(),
        )
        self.logger.printf(
            "pilosa-tpu listening on %s:%d (node %s)", host, port, self.node_id
        )
        # After serve(): process-mode sampling needs api.process_server
        # and the handler, both wired by serve().
        self._setup_observability()
        self._start_monitors()
        return self

    def _server_opts(self) -> dict:
        """Serving-tier knobs for bind_http (docs/serving.md): backend
        selection plus the event-loop server's reactor/pool/parse
        bounds.  The threaded backend consumes only ``backend``."""
        cfg = self.config
        opts = {"backend": cfg.server_backend}
        if cfg.server_backend != "threaded":
            opts.update(
                reactors=cfg.server_reactors,
                workers=cfg.server_workers,
                pool_workers=cfg.server_pool_workers,
                queue_depth=cfg.server_queue_depth,
                max_body_bytes=cfg.server_max_body_bytes,
                read_timeout=cfg.server_read_timeout,
                idle_timeout=cfg.server_idle_timeout,
            )
            if cfg.server_workers > 0:
                # Process mode terminates TLS in the workers, which need
                # the PATHS (an SSLContext can't cross the fork).
                opts.update(
                    tls_certificate=cfg.tls_certificate,
                    tls_key=cfg.tls_key,
                )
        return opts

    def _make_admission(self):
        """Admission controller for the event-loop backend; None keeps
        the threaded oracle admission-free (its thread-per-connection
        model is the differential baseline)."""
        if self.config.server_backend == "threaded":
            return None
        from .net.admission import AdmissionController, _parse_weights

        return AdmissionController(
            max_inflight=self.config.server_max_inflight,
            fair_start=self.config.server_fair_start,
            weights=_parse_weights(self.config.server_tenant_weights),
        )

    def _node_devices(self) -> int:
        """This node's placement weight: the device count of the LOCAL
        (addressable) slice of the shard mesh.  Advertised via gossip
        node metadata so capacity-weighted shard ownership
        (cluster.place_partition) gives an 8-chip host 8x the shards of
        a 1-chip host — its in-mesh psum then covers them with zero
        extra network hops (docs/mesh.md).  1 in the explicit host-only
        mode (``[mesh] devices = -1``); otherwise unreachable devices
        fail the open, like the engine build they precede."""
        if self.config.mesh_devices < 0:
            return 1
        import jax

        n = jax.local_device_count()
        if self.config.mesh_devices and jax.process_count() == 1:
            # A single-process mesh trimmed by [mesh] devices owns
            # only the trimmed slice.
            n = min(n, self.config.mesh_devices)
        return max(1, int(n))

    def _make_mesh_engine(self):
        """Fused device query path over the local mesh (parallel
        package).  ``[mesh] devices = -1`` is the explicit host-only
        mode and returns None; with ``devices >= 0`` an engine that
        cannot be built raises and fails ``open()`` — a node must not
        come up answering every query from the per-shard host loop
        while /readyz says 200.

        With ``--jax-coordinator`` the JAX distributed runtime is
        initialized FIRST (the analogue of setupNetworking,
        server/server.go:302-331) so the mesh spans every host's devices;
        collective dispatches are then replayed on the configured peer
        servers so the psum can rendezvous (SPMD serving)."""
        if self.config.mesh_devices < 0:
            return None
        from . import compile_cache
        from .parallel import MeshEngine, make_mesh
        from .parallel.mesh import describe

        cache_dir = compile_cache.configure()
        if self.config.jax_coordinator:
            # jax.distributed is up (see _open_bound): the mesh spans
            # every host's devices; collectives ride ICI/DCN while
            # the cluster control plane stays per-host HTTP/gossip.
            from .parallel import multihost

            mesh = multihost.global_mesh(self.config.mesh_devices or None)
        else:
            mesh = make_mesh(self.config.mesh_devices or None)
        where = describe(mesh)
        self.logger.printf(
            "mesh: platform=%s deviceKind=%s devices=%d compileCache=%s",
            where["platform"], where["deviceKind"], where["devices"],
            cache_dir,
        )
        kwargs = {}
        if self.config.engine_device_budget_bytes > 0:
            kwargs["max_resident_bytes"] = (
                self.config.engine_device_budget_bytes
            )
        engine = MeshEngine(
            self.holder, mesh, logger=self.logger, journal=self.journal,
            **kwargs,
        )
        # Seed the residency/warm-start cost signal from the last
        # run's persisted per-tenant device-cost EWMAs
        # (docs/residency.md): a restarted node re-warms its HOT
        # tenants' stacks first instead of holder iteration order.
        self._load_tenant_costs()
        if self.config.mesh_peers:
            from concurrent.futures import ThreadPoolExecutor

            self._mesh_pool = ThreadPoolExecutor(
                max_workers=max(4, len(self.config.mesh_peers)),
                thread_name_prefix="mesh-peer",
            )
            engine.collective_broadcast = self._broadcast_dispatch
        return engine

    def _make_ticket_fn(self):
        """Collective sequence tickets (symmetric initiation): local
        counter when this node IS the sequencer, one HTTP round-trip to
        the sequencer node otherwise."""
        target = self.config.mesh_sequencer
        if target == "self":
            return lambda: self.api.mesh_ticket()
        import urllib.request

        def fetch():
            # _make_client: honors tls.skip-verify on https meshes.
            # 10s cap: a dead sequencer must not stall dispatchers for
            # the full default client timeout.
            doc = self._make_client(target, timeout=10.0)._post(
                "/internal/mesh/ticket", {}
            )
            return int(doc["seq"])

        return fetch

    def _broadcast_dispatch(self, kind, payload):
        """Two-phase handoff of a collective dispatch descriptor to every
        peer server.  Phase 1 (accept): peers validate and REGISTER the
        dispatch but do not enter it — a peer that is down or rejects
        raises NOW, and the others get an abort, so a partial fan-out can
        never strand anyone in a collective no peer will join.  Phase 2
        (commit): sent only after every peer accepted; peers then enqueue
        the replay.  A peer that accepted but never hears a commit (this
        process died mid-handoff) expires its pending entry instead of
        dispatching (api.MESH_PENDING_TIMEOUT)."""
        import urllib.request

        did = uuid.uuid4().hex

        def post(url, body):
            self._make_client(
                url, timeout=self.config.mesh_dispatch_timeout
            )._do(
                "POST", "/internal/mesh/dispatch", body,
                content_type="application/json",
            )

        def fanout(body):
            futures = [
                self._mesh_pool.submit(post, url, body)
                for url in self.config.mesh_peers
            ]
            errs = []
            for url, f in zip(self.config.mesh_peers, futures):
                try:
                    f.result(timeout=35)
                except Exception as e:
                    errs.append(f"{url}: {e}")
            return errs

        accept = json.dumps(
            dict(payload, kind=kind, did=did, phase="accept")
        ).encode()
        # The abort/commit resolutions carry the ticket too: a peer that
        # REJECTED the accept never registered the did, but its seq gate
        # still has to skip the ticket other peers took into their
        # streams (api._mesh_collective_resolve).
        resolution = {"did": did}
        if payload.get("seq") is not None:
            resolution["seq"] = payload["seq"]
        errs = fanout(accept)
        if errs:
            # Release the peers that DID accept; best-effort — a peer the
            # abort misses expires the pending entry on its own timer.
            fanout(json.dumps(dict(resolution, phase="abort")).encode())
            raise RuntimeError(f"mesh peers unavailable: {'; '.join(errs)}")
        errs = fanout(json.dumps(dict(resolution, phase="commit")).encode())
        if errs:
            # Commits are idempotent-or-expired: peers the commit missed
            # time out and abort; peers it reached replay a collective
            # this process must NOT join (it would complete without the
            # timed-out peer only by luck) — so fail the query loudly.
            raise RuntimeError(
                f"mesh commit failed (peers will expire): {'; '.join(errs)}"
            )

    def _setup_cluster(self, host: str, port: int):
        """Wire the cluster when hosts, gossip seeds, or the coordinator
        role are configured (server/server.go setupNetworking :302);
        single-node otherwise.  The coordinator case matters for
        bootstrap: the FIRST node of a gossip-joined cluster has no
        seeds and no static host list, but must still start its gossip
        listener for followers to join."""
        if self.config.cluster_disabled or not (
            self.config.cluster_hosts
            or self.config.gossip_seeds
            or self.config.cluster_coordinator
        ):
            return
        from .cluster import Cluster, Node

        uri = _advertise_uri(host, port, self.scheme)
        self.cluster = Cluster(
            node=Node(
                self.node_id, uri, self.config.cluster_coordinator,
                devices=self._node_devices(),
            ),
            replica_n=self.config.cluster_replicas,
            hosts=self.config.cluster_hosts,
            path=self.data_dir,
            client_factory=self._make_client,
            logger=self.logger,
            journal=self.journal,
        )
        # Replica-read routing policy (docs/durability.md).
        self.cluster.replica_read = self.config.cluster_replica_read
        self.cluster.freshness_ms = self.config.cluster_freshness_ms
        self.cluster.recovery_holddown = (
            float(self.config.cluster_recovery_holddown_ms) / 1000.0
        )
        # Hinted handoff (docs/durability.md): durable bounded replay
        # queues for writes to DOWN owners; hint-max-bytes 0 keeps the
        # pre-hint skip-or-fail-loud policy.
        if int(self.config.cluster_hint_max_bytes) > 0:
            from .cluster.hints import HintManager

            self.hints = HintManager(
                self.data_dir,
                node_id=self.node_id,
                max_bytes=self.config.cluster_hint_max_bytes,
                max_age=self.config.cluster_hint_max_age,
                ack=self.config.storage_ack,
                journal=self.journal,
                logger=self.logger,
            )
            self.hints.cluster = self.cluster
            self.cluster.hints = self.hints
            self.hints.start()
        if (
            not self.config.cluster_hosts
            and not self.config.gossip_seeds
            and len(self.cluster.nodes) <= 1
        ):
            # Lone bootstrap coordinator: serve NORMAL immediately (one
            # READY node is a healthy cluster of one); followers joining
            # later re-run the state machine via membership events.  The
            # node-count check matters on RESTART: a persisted .topology
            # may have restored absent peers, and those must re-form via
            # membership before the cluster reports healthy.
            self.cluster._determine_state()
        self._setup_gossip(uri)

    def _setup_gossip(self, uri: str):
        """SWIM membership feeding cluster join/leave events
        (gossip/gossip.go eventReceiver :317-396)."""
        from .cluster import Node
        from .cluster.gossip import GossipNode

        cluster = self.cluster

        # Membership events drain through a serialized worker (the
        # reference's joiningLeavingNodes channel + listenForJoins
        # goroutine, cluster.go:1095-1145): a join that triggers a
        # resize JOB blocks until the job completes, and that must never
        # stall the SWIM probe/ack loop the callbacks run on.
        import queue as queue_mod

        events: "queue_mod.Queue" = queue_mod.Queue()

        def membership_worker():
            while True:
                item = events.get()
                if item is None:
                    return
                kind, member = item
                try:
                    if kind == "join":
                        cluster.add_node(
                            Node(
                                member.id,
                                member.meta.get("uri"),
                                member.meta.get("coordinator", False),
                                devices=member.meta.get("devices", 1),
                            )
                        )
                    else:
                        cluster.node_failed(member.id)
                except Exception as e:
                    self.logger.printf(
                        "membership %s for %s failed: %s", kind, member.id, e
                    )

        self._membership_events = events
        t = threading.Thread(
            target=membership_worker, daemon=True, name="membership"
        )
        t.start()
        self._monitors.append(t)

        def on_join(member):
            if member.meta.get("uri"):
                events.put(("join", member))

        def on_leave(member):
            events.put(("leave", member))

        def on_message(payload):
            # Gossip-delivered cluster messages (SendAsync receive path)
            # dispatch like HTTP /internal/cluster/message bodies.
            if isinstance(payload, dict) and self.api is not None:
                try:
                    self.api.cluster_message(payload)
                except Exception as e:
                    self.logger.printf("gossip message failed: %s", e)

        self.gossip = GossipNode(
            self.node_id,
            meta={
                "uri": uri,
                "coordinator": self.config.cluster_coordinator,
                # Placement weight: capacity-weighted shard ownership
                # reads this from every member's metadata.
                "devices": self.cluster.node.devices,
            },
            port=self.config.gossip_port,
            probe_interval=self.config.gossip_probe_interval,
            probe_timeout=self.config.gossip_probe_timeout,
            suspicion_mult=self.config.gossip_suspicion_mult,
            on_join=on_join,
            on_leave=on_leave,
            on_message=on_message,
            # Direct-liveness evidence feeds the freshness registry
            # bounded replica reads consult (docs/durability.md).
            on_alive=cluster.note_heartbeat,
            logger=self.logger,
            journal=self.journal,
        ).start()
        cluster.gossip_send_async = self.gossip.send_async
        if self.config.gossip_seeds:
            # Seed joins RETRY in the background until another member is
            # known: a one-shot join silently strands a node that boots
            # before its seed (concurrent cluster bring-up — the normal
            # case under an orchestrator).  The reference's memberlist
            # Join is likewise driven until it reports contact
            # (gossip/gossip.go joinWithRetry pattern).
            def join_seeds():
                deadline = time.monotonic() + 120.0
                while (
                    not self._closing.is_set()
                    and time.monotonic() < deadline
                ):
                    for seed in self.config.gossip_seeds:
                        h, _, p = seed.rpartition(":")
                        try:
                            self.gossip.join((h or "127.0.0.1", int(p)))
                        except Exception as e:
                            self.logger.debugf("seed join failed: %s", e)
                    if len(self.gossip.members) > 1:
                        return
                    time.sleep(0.5)

            t = threading.Thread(
                target=join_seeds, daemon=True, name="gossip-join"
            )
            t.start()
            self._monitors.append(t)

    def _setup_faults(self, host: str, port: int):
        """Stamp this node's identity onto the process-global fault
        plane (partition-group membership tests against it) and install
        any boot-time [faults] rules.  Identity = node id + advertised
        HTTP endpoint + bound gossip endpoint, so one partition body
        POSTed to every node lets each enforce only its own side."""
        from .net.faults import PLANE

        addrs = {self.node_id, _advertise_uri(host, port, self.scheme)}
        if getattr(self, "gossip", None) is not None:
            addrs.add(f"{self.gossip.addr[0]}:{self.gossip.addr[1]}")
        PLANE.set_local(addrs)
        if self.config.faults_rules:
            PLANE.configure(
                self.config.faults_rules, self.config.faults_seed
            )
            self.journal.append(
                "faults.configure", rules=len(self.config.faults_rules),
                seed=self.config.faults_seed, via="config",
            )

    @property
    def scheme(self) -> str:
        """'https' when TLS serving is configured, else 'http' — the
        scheme every advertised URI carries (server/server.go:204-214)."""
        return "https" if self.config.tls_certificate else "http"

    def _make_client(self, uri: str, timeout: float = 30.0):
        """Cluster-internal client honoring tls.skip-verify for
        self-signed deployments (http/client.go GetHTTPClient).  Cached
        per (uri, timeout): on https the skip-verify SSLContext loads
        the system CA bundle from disk, far too expensive to rebuild on
        the per-second replication poll or per-dispatch ticket fetch."""
        from .net import InternalClient

        key = (uri, timeout)
        c = self._client_cache.get(key)
        if c is None:
            c = InternalClient(
                uri, timeout=timeout,
                tls_skip_verify=self.config.tls_skip_verify,
                # Per-attempt socket bound < the whole-request deadline:
                # a black-holed dial to a mid-restart peer must leave
                # deadline for the backoff budget (and the mapper's
                # hedge) to engage, instead of one connect eating the
                # full timeout (docs/durability.md).
                attempt_timeout=min(10.0, timeout),
            )
            self._client_cache[key] = c
        return c

    @property
    def port(self) -> int:
        return self._http.server_address[1]

    def _setup_observability(self):
        """Self-hosted metrics history + SLO watcher
        (docs/observability.md): a background tick samples every
        registry series into the ``_system`` index (util/history.py)
        and evaluates the configured SLO burn rates against it
        (util/slo.py).  Off unless ``[observability] history = true`` —
        the sampler writes through the normal import path every tick,
        so tests and minimal deployments opt in."""
        cfg = self.config
        if not cfg.obs_history:
            return
        from .util.history import HistorySampler
        from .util.slo import SLOWatcher

        snapshot_fn = None
        ps = self.api.process_server
        if ps is not None:
            # Process mode: one history for the whole NODE — sample the
            # same aggregated exposition /metrics serves (engine process
            # + every worker registry summed at scrape time), parsed
            # back into snapshot shape.
            from .util.stats import snapshot_from_exposition

            handler = getattr(
                getattr(self._http, "RequestHandlerClass", None),
                "handler", None,
            )
            if handler is not None:
                snapshot_fn = lambda: snapshot_from_exposition(  # noqa: E731
                    ps.aggregate_metrics(handler)
                )
        self.api.history = HistorySampler(
            self.api,
            node=self.node_id,
            interval=cfg.obs_sample_interval,
            retention=cfg.obs_retention,
            snapshot_fn=snapshot_fn,
        )
        self.api.slo = SLOWatcher(
            self.api,
            self.api.history,
            node=self.node_id,
            error_rate_target=cfg.obs_slo_error_rate,
            latency_p95_ms_target=cfg.obs_slo_latency_p95_ms,
            window=cfg.obs_slo_window,
            burn_threshold=cfg.obs_slo_burn_threshold,
            data_dir=self.data_dir,
            max_bundles=cfg.obs_flightrec_max_bundles,
        )
        self.journal.append(
            "observability.start",
            interval=cfg.obs_sample_interval,
            retention=cfg.obs_retention,
            processMode=ps is not None,
        )
        self._spawn(self._observability_tick, cfg.obs_sample_interval)

    def _observability_tick(self):
        self.api.history.tick()
        self.api.slo.tick()

    def _start_monitors(self):
        # Overlapped warm-start (docs/durability.md): re-establish HBM
        # residency from the just-opened snapshots on a background
        # thread while this node ALREADY answers from the host path;
        # /readyz reports `warming` with a residency fraction until the
        # working set is resident.
        eng = self.api.mesh_engine if self.api is not None else None
        if (
            self.config.storage_warm_start
            and eng is not None
            and self.holder.indexes
        ):
            t = threading.Thread(
                target=self._warm_start, daemon=True, name="warm-start"
            )
            t.start()
            self._monitors.append(t)
        # Cache flush ticker (holder.go cacheFlushInterval :78).
        self._spawn(self._monitor_cache_flush, 60.0)
        # Runtime metrics loop (server.go monitorRuntime :726).
        if self.config.metric_poll_interval > 0:
            self._spawn(self._monitor_runtime, self.config.metric_poll_interval)
        if self.cluster is not None:
            self.start_anti_entropy()
        # Diagnostics loop (server.go monitorDiagnostics :675); endpoint
        # unset by default so nothing leaves the host.
        if self.config.metric_diagnostics:
            from .util.diagnostics import Diagnostics

            self.diagnostics = Diagnostics(
                api=self.api,
                logger=self.logger,
                version_url=self.config.diagnostics_version_url,
            ).start()
        # Translate-store replication from the primary (translate.go
        # monitorReplication :358-432).
        if self.config.translation_primary_url:
            self.translate_store.read_only = True
            self._spawn(self._replicate_translate, 1.0)

    def _warm_start(self):
        try:
            ws = self.api.mesh_engine.warm_start()
            self.logger.printf(
                "warm-start done: %d/%d stacks resident (%d skipped)",
                ws["built"], ws["total"], ws["skipped"],
            )
        except Exception as e:  # noqa: BLE001 — warming must not kill boot
            self.logger.printf("warm-start failed: %s", e)
            eng = self.api.mesh_engine
            ws = getattr(eng, "warm_state", None)
            if ws is not None:
                ws["done"] = True  # never pin readyz on a failed warm

    def _replicate_translate(self):
        client = self._make_client(self.config.translation_primary_url)
        data = client.translate_data(self.translate_store.size())
        if data:
            self.translate_store.apply_log(data)

    def start_anti_entropy(self, interval: Optional[float] = None):
        """Spawn the anti-entropy loop (server.go monitorAntiEntropy
        :430-483).  Callable after a late cluster attach (test harness)."""
        from .cluster.syncer import HolderSyncer

        self.syncer = HolderSyncer(
            self.holder, self.cluster, self.logger, journal=self.journal
        )

        def sync_and_clean():
            self.syncer.sync_holder()
            # Drop fragments this node no longer owns (holder.go
            # holderCleaner :852-902).
            self.cluster.clean_holder()
            # Re-exchange NodeStatus (schema + per-field available shards)
            # over the reliable fan-out: a create-shard gossip broadcast
            # whose retransmit budget drained before reaching some node is
            # repaired here within one anti-entropy interval
            # (server.go NodeStatus :626-674).
            self.cluster.send_sync(self.cluster.node_status())

        self._spawn(
            sync_and_clean,
            interval
            if interval is not None
            else self.config.anti_entropy_interval,
        )

    def _spawn(self, fn, interval: float):
        def loop():
            while not self._closing.wait(interval):
                try:
                    fn()
                except Exception as e:  # monitors never kill the server
                    self.logger.printf("monitor error: %s", e)

        t = threading.Thread(target=loop, daemon=True)
        t.start()
        self._monitors.append(t)

    def _monitor_cache_flush(self):
        # Snapshots: an import creating fragments mid-walk must not void
        # the tick ("dictionary changed size during iteration").
        for idx in list(self.holder.indexes.values()):
            for f in list(idx.fields.values()):
                for v in list(f.views.values()):
                    for frag in list(v.fragments.values()):
                        frag.flush_cache()
        # Piggyback the per-tenant device-cost EWMA persistence on the
        # flush tick: the snapshot is tiny (<=256 tenants) and feeds the
        # NEXT boot's warm-start ordering (docs/residency.md).
        self._save_tenant_costs()

    # Persisted per-tenant device-cost EWMAs (docs/residency.md): the
    # warm-start ordering signal survives restarts.
    TENANT_COSTS_FILE = ".tenant_costs"

    def _tenant_costs_path(self) -> str:
        return os.path.join(self.data_dir, self.TENANT_COSTS_FILE)

    def _save_tenant_costs(self):
        from .util import plans as plans_mod

        try:
            snap = plans_mod.LEDGER.ewma_snapshot()
            if not snap:
                return
            import json as json_mod

            tmp = self._tenant_costs_path() + ".tmp"
            with open(tmp, "w") as f:
                json_mod.dump(
                    {t: round(v, 9) for t, v in snap.items()}, f
                )
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self._tenant_costs_path())
        except Exception as e:  # noqa: BLE001 — telemetry persistence
            self.logger.printf("tenant-cost snapshot failed: %s", e)

    def _load_tenant_costs(self):
        from .util import plans as plans_mod

        try:
            with open(self._tenant_costs_path()) as f:
                import json as json_mod

                doc = json_mod.load(f)
            if isinstance(doc, dict):
                plans_mod.LEDGER.seed_costs(doc)
        except FileNotFoundError:
            pass
        except Exception as e:  # noqa: BLE001 — corrupt snapshot: cold order
            self.logger.printf("tenant-cost snapshot unreadable: %s", e)

    def _monitor_runtime(self):
        """Runtime metrics loop (server.go monitorRuntime :726-790:
        goroutines/GC/open-FDs become threads/gc-collections/open-FDs)."""
        import gc
        import resource

        usage = resource.getrusage(resource.RUSAGE_SELF)
        self.stats.gauge("maxrss_kb", usage.ru_maxrss)
        self.stats.gauge("threads", threading.active_count())
        for gen, st in enumerate(gc.get_stats()):
            self.stats.gauge(f"gc.gen{gen}.collections", st["collections"])
        try:
            self.stats.gauge("openFiles", len(os.listdir("/proc/self/fd")))
        except OSError:
            pass

    def close(self):
        self._closing.set()
        self.journal.append("server.shutdown", node=self.node_id)
        # Persist the warm-start ordering signal before teardown.
        self._save_tenant_costs()
        if getattr(self, "_membership_events", None) is not None:
            self._membership_events.put(None)
        if getattr(self, "gossip", None) is not None:
            self.gossip.close()
        if self.hints is not None:
            # Stop the replay worker and flush the queue files: pending
            # hints are DURABLE — a restart reloads and resumes replay.
            self.hints.close()
        # Close ORDER is load-bearing for shutdown scrapes: the mesh
        # engine closes only AFTER the HTTP socket stops accepting, and
        # engine.close() itself flushes the resident-bytes gauges under
        # its lock — so a /metrics scrape racing shutdown either reads
        # pre-close truth or flushed zeros, never a stale value against
        # a closed socket.
        if self._http is not None:
            if self._http_thread is not None:
                # shutdown() waits on an event only serve_forever() sets
                # — calling it on a bound-but-never-served socket (open()
                # failed mid-way) deadlocks (socketserver.BaseServer).
                self._http.shutdown()
            self._http.server_close()
        # Release the mesh engine's device-buffer caches (resident field
        # stacks, masks, scalars, result memo) BEFORE the holder closes:
        # HBM is returned deterministically at shutdown instead of
        # whenever the engine object happens to be collected.
        if self.api is not None and getattr(self.api, "mesh_engine", None) is not None:
            try:
                self.api.mesh_engine.close()
            except Exception as e:  # noqa: BLE001 — teardown must not raise
                self.logger.printf("mesh engine close failed: %s", e)
            # The registry must render after engine teardown (a scrape
            # that slipped in through the draining socket must not see a
            # half-torn-down registry): render it once and fail LOUDLY
            # in the log if it cannot.
            try:
                from .util.stats import REGISTRY

                REGISTRY.prometheus_text()
            except Exception as e:  # noqa: BLE001
                self.logger.printf(
                    "metrics registry unreadable after engine close: %s", e
                )
        self.holder.close()
        self.translate_store.close()
