"""Node: wires all services together (the reference's Node container,
ref: node/Node.java:280-686 — constructs and binds every service, manages
lifecycle start/stop/close). Single-node for now; the cluster layer
(coordination, replication) attaches here as it lands.
"""

from __future__ import annotations

import logging
import os
import uuid
from collections import OrderedDict
from typing import Optional

logger = logging.getLogger("elasticsearch_tpu.node")

from elasticsearch_tpu.common.settings import Setting, Settings
from elasticsearch_tpu.index.service import IndicesService
from elasticsearch_tpu.index.metadata import MetadataService
from elasticsearch_tpu.ingest.service import IngestService
from elasticsearch_tpu.repositories.blobstore import RepositoriesService
from elasticsearch_tpu.snapshots.slm import SnapshotLifecycleService
from elasticsearch_tpu.rest.api import RestController
from elasticsearch_tpu.rest.http_server import HttpServer
from elasticsearch_tpu.search.async_search import AsyncSearchService
from elasticsearch_tpu.search.script import StoredScripts
from elasticsearch_tpu.search.service import SearchService
from elasticsearch_tpu.transport.tasks import TaskManager
from elasticsearch_tpu.utils.breaker import HierarchyCircuitBreakerService

NODE_NAME_SETTING = Setting.str_setting("node.name", None)
CLUSTER_NAME_SETTING = Setting.str_setting("cluster.name", "elasticsearch-tpu")
PATH_DATA_SETTING = Setting.str_setting("path.data", "data")
HTTP_PORT_SETTING = Setting.int_setting("http.port", 9200)


class Node:
    def __init__(self, settings: Settings = Settings.EMPTY,
                 data_path: Optional[str] = None):
        self.settings = settings
        self.node_id = uuid.uuid4().hex[:20]
        self.name = NODE_NAME_SETTING.get(settings) or self.node_id[:7]
        self.cluster_name = CLUSTER_NAME_SETTING.get(settings)
        self.data_path = data_path or PATH_DATA_SETTING.get(settings)
        os.makedirs(self.data_path, exist_ok=True)
        # secure-settings keystore (ref: KeyStoreWrapper loaded at
        # bootstrap, node/Node.java:389-391): loaded from the node dir
        # when present; password via ES_KEYSTORE_PASSPHRASE
        from elasticsearch_tpu.common.keystore import (
            KEYSTORE_FILENAME, KeyStore)
        self.keystore: Optional[KeyStore] = None
        ks_path = os.path.join(self.data_path, KEYSTORE_FILENAME)
        if os.path.exists(ks_path):
            self.keystore = KeyStore(ks_path).load(
                os.environ.get("ES_KEYSTORE_PASSPHRASE", ""))
        # memory protection: hierarchical circuit breakers + in-flight
        # indexing-byte admission, limits from the node settings
        # (`indices.breaker.*.limit` / `indexing_pressure.memory.limit`
        # — parsing/defaulting shared with ClusterNode)
        from elasticsearch_tpu.index.pressure import IndexingPressure
        from elasticsearch_tpu.utils.breaker import build_breaker_service
        self.breaker_service = build_breaker_service(settings.get)
        self.indexing_pressure = IndexingPressure.from_settings(
            settings.get)
        # named executors with EWMA task tracking (ref:
        # ThreadPool.java:117-181, wired ahead of every service)
        from elasticsearch_tpu.common.threadpool import ThreadPool
        self.threadpool = ThreadPool()
        # node telemetry: metrics registry + tracer (telemetry/), the
        # `_nodes/stats` telemetry section and the /_traces surface;
        # trace retention is bounded (max traces x max spans per trace)
        # and tunable for long-running nodes
        from elasticsearch_tpu.telemetry import Telemetry
        self.telemetry = Telemetry(
            node=self.name,
            max_traces=int(settings.get("telemetry.traces.max", 128)),
            max_spans_per_trace=int(
                settings.get("telemetry.traces.max_spans", 512)),
            history_interval=float(
                settings.get("telemetry.history.interval", 10.0)),
            history_retention=float(
                settings.get("telemetry.history.retention", 600.0)))
        # breaker trips + indexing-pressure rejections feed the node
        # metrics registry (`breaker.*` / `indexing_pressure.*`)
        self.breaker_service.metrics = self.telemetry.metrics
        self.indexing_pressure.metrics = self.telemetry.metrics
        # tenant accounting (telemetry/tenants.py): cap + SLO
        # objectives from settings; breaker trips and indexing bytes /
        # rejections are charged to the ambient tenant through it
        from elasticsearch_tpu.telemetry.tenants import TenantAccounting
        self.telemetry.tenants = TenantAccounting.from_settings(
            settings.get, self.telemetry.metrics,
            history=self.telemetry.history)
        self.telemetry.flight.tenants = self.telemetry.tenants
        self.breaker_service.tenants = self.telemetry.tenants
        self.indexing_pressure.tenants = self.telemetry.tenants
        # workload-class accounting (telemetry/workload.py): the
        # request-kind half of the same attribution rail
        from elasticsearch_tpu.telemetry.workload import (
            WorkloadAccounting)
        self.telemetry.workload = WorkloadAccounting.from_settings(
            settings.get, self.telemetry.metrics,
            history=self.telemetry.history)
        self.telemetry.flight.workloads = self.telemetry.workload
        self.indexing_pressure.workloads = self.telemetry.workload
        self.indices_service = IndicesService(self.data_path, settings)
        # the shared device cache charges the `hbm` child breaker on
        # segment/filter-mask admission (LRU eviction pressure first),
        # and hands searchers a request-breaker-accounted BigArrays for
        # host staging/readback buffers
        from elasticsearch_tpu.utils.bigarrays import BigArrays
        from elasticsearch_tpu.utils.breaker import CircuitBreaker
        self.indices_service.device_cache.set_breaker(
            self.breaker_service.get_breaker(CircuitBreaker.HBM))
        self.indices_service.device_cache.bigarrays = BigArrays(
            self.breaker_service)
        self.search_service = SearchService(self.indices_service)
        self.search_service.telemetry = self.telemetry
        # batcher cohort-slot attribution: each enqueued entry charges
        # one slot to its tenant (search/batching.py)
        self.search_service.plan_batcher.tenants = self.telemetry.tenants
        self.search_service.knn_batcher.tenants = self.telemetry.tenants
        self.search_service.plan_batcher.workloads = \
            self.telemetry.workload
        self.search_service.knn_batcher.workloads = \
            self.telemetry.workload
        # queue-wait and re-rank histograms (`knn.*`, `GET /_nodes/stats`)
        self.search_service.knn_batcher.metrics = self.telemetry.metrics
        # mesh serving backend: dispatch/fallback counters mirror into
        # the node registry (search.mesh.dispatch{axis} /
        # search.mesh.fallback{reason}) next to its own stats surface
        # in GET /_kernels
        self.search_service.mesh_executor.metrics = self.telemetry.metrics
        # tasks.started/completed/cancelled counters + the live task
        # gauge feed the node metrics registry
        self.task_manager = TaskManager(self.node_id,
                                        metrics=self.telemetry.metrics)
        # health & diagnostics: the single-node slice of the cluster
        # health surface (GET /_health_report) — no routing table here,
        # so shards_availability reports green-by-construction; the
        # watchdog sweeps lazily per report (no scheduler on this node)
        from elasticsearch_tpu.health import (
            HealthContext, HealthService, StalledProgressWatchdog)
        from elasticsearch_tpu.health import watchdog as _watchdog_mod
        self.health_watchdog = StalledProgressWatchdog(
            clock=self.telemetry.metrics.clock,
            metrics=self.telemetry.metrics,
            tasks_fn=self.task_manager.list_tasks,
            stall_after_s=float(settings.get(
                "health.watchdog.stall_after",
                _watchdog_mod.DEFAULT_STALL_AFTER_S)),
            task_deadline_s=float(settings.get(
                "health.watchdog.task_deadline",
                _watchdog_mod.DEFAULT_TASK_DEADLINE_S)))

        def _health_context(_self=self):
            from elasticsearch_tpu.telemetry import engine as _engine
            return HealthContext(
                node_id=_self.node_id,
                now=_self.telemetry.metrics.clock,
                metrics=_self.telemetry.metrics,
                history=_self.telemetry.history,
                breaker_service=_self.breaker_service,
                indexing_pressure=_self.indexing_pressure,
                task_manager=_self.task_manager,
                engine_totals=_engine.TRACKER.totals(),
                mesh_stats=_self.search_service.mesh_executor.stats(),
                watchdog=_self.health_watchdog,
                flight=_self.telemetry.flight,
                tenants=_self.telemetry.tenants,
                workload=_self.telemetry.workload,
                repositories=_self.repositories_service)

        self.health = HealthService(context_fn=_health_context)
        # completed background-task responses (ref: the .tasks results
        # index); bounded — oldest entries evicted beyond 256
        self.task_results: "OrderedDict[int, dict]" = OrderedDict()
        self.async_search_service = AsyncSearchService(
            self.search_service, self.task_manager)
        self.ingest_service = IngestService(self.data_path)
        self.stored_scripts = StoredScripts(self.data_path)
        # stored-script resolver hook: a weakref so a closed node's
        # scripts (and data-path state) are never pinned process-wide
        import weakref
        from elasticsearch_tpu.search import queries as _queries_mod
        _ss_ref = weakref.ref(self.stored_scripts)

        def _resolve(script_id, _r=_ss_ref):
            ss = _r()
            return ss.get(script_id) if ss is not None else None
        _queries_mod.STORED_SCRIPT_RESOLVER = _resolve
        self._stored_script_resolver = _resolve
        self.metadata_service = MetadataService(self.indices_service,
                                                self.data_path)
        # cloud repository credentials resolve from the node keystore
        from elasticsearch_tpu.repositories import blobstore as _bs
        if self.keystore is not None:
            _bs.NODE_KEYSTORES[self.data_path] = self.keystore
        self.repositories_service = RepositoriesService(self.data_path)
        # searchable snapshots: mounted shards fetch segments lazily
        # through the node blob cache (ref: SearchableSnapshotDirectory;
        # xpack/searchable_snapshots.py)
        from elasticsearch_tpu.index import engine as _engine_mod
        from elasticsearch_tpu.xpack import searchable_snapshots as _ss
        _engine_mod.LAZY_MATERIALIZERS[self.data_path] = (
            lambda shard_path, seg: _ss.materialize_segment(
                shard_path, seg, self.repositories_service,
                self.data_path))
        self.slm_service = SnapshotLifecycleService(
            self.repositories_service, self.indices_service, self.data_path)
        from elasticsearch_tpu.xpack.ilm import IndexLifecycleService
        self.ilm_service = IndexLifecycleService(
            self.indices_service, self.metadata_service,
            self.repositories_service, self.data_path, self.slm_service)
        from elasticsearch_tpu.transport.persistent import (
            PersistentTasksService)
        self.persistent_tasks = PersistentTasksService(self.data_path)
        from elasticsearch_tpu.xpack.transform import TransformService
        self.transform_service = TransformService(
            self.indices_service, self.search_service,
            self.persistent_tasks, self.data_path)
        from elasticsearch_tpu.xpack.security import SecurityService
        anon_roles = settings.get("xpack.security.authc.anonymous.roles")
        if isinstance(anon_roles, str):
            anon_roles = [r.strip() for r in anon_roles.split(",")
                          if r.strip()]
        anon_user = settings.get(
            "xpack.security.authc.anonymous.username")
        if anon_user is None and anon_roles:
            # roles alone enable anonymous access; the principal name
            # defaults like the reference's AnonymousUser
            anon_user = "_anonymous"
        # bootstrap.password is a SECURE setting: keystore-only in the
        # reference (ref: ReservedRealm BOOTSTRAP_ELASTIC_PASSWORD); the
        # plain-settings fallback stays for compatibility but the
        # keystore value wins and plain+keystore together is an error
        from elasticsearch_tpu.common.keystore import secure_setting
        boot_pw_setting = secure_setting("bootstrap.password",
                                         consistent=True)
        if self.keystore is not None and self.keystore.has(
                "bootstrap.password"):
            boot_pw = boot_pw_setting.get(settings, self.keystore)
        else:
            boot_pw = str(settings.get("bootstrap.password", "changeme"))
        self.security_service = SecurityService(
            self.data_path,
            enabled=bool(settings.get("xpack.security.enabled", False)),
            bootstrap_password=boot_pw,
            anonymous_username=anon_user,
            anonymous_roles=anon_roles,
            audit_enabled=bool(
                settings.get("xpack.security.audit.enabled", False)),
            pki_header_trusted=bool(settings.get(
                "xpack.security.authc.pki.trust_proxy_header", False)),
            pki_truststore=settings.get(
                "xpack.security.authc.pki.truststore", None),
            keystore=self.keystore,
            jwt_issuer=settings.get(
                "xpack.security.authc.jwt.allowed_issuer"),
            jwt_audience=settings.get(
                "xpack.security.authc.jwt.allowed_audiences"),
            ldap_config={
                k: settings.get(f"xpack.security.authc.ldap.{k}")
                for k in ("url", "user_dn_templates", "bind_dn",
                          "bind_password", "user_search_base",
                          "user_search_attribute", "group_search_base",
                          "timeout")
                if settings.get(
                    f"xpack.security.authc.ldap.{k}") is not None},
            oidc_config={
                k: settings.get(f"xpack.security.authc.oidc.{k}")
                for k in ("op.issuer", "op.jwks_path", "rp.client_id",
                          "claims.principal", "claims.groups")
                if settings.get(
                    f"xpack.security.authc.oidc.{k}") is not None},
            saml_config={
                k: settings.get(f"xpack.security.authc.saml.{k}")
                for k in ("idp.entity_id", "idp.certificate",
                          "idp.sso_url", "sp.entity_id", "sp.acs",
                          "attributes.principal", "attributes.groups",
                          "clock_skew")
                if settings.get(
                    f"xpack.security.authc.saml.{k}") is not None},
            kerberos_config={
                k: settings.get(f"xpack.security.authc.kerberos.{k}")
                for k in ("keytab_path", "remove_realm_name")
                if settings.get(
                    f"xpack.security.authc.kerberos.{k}") is not None})
        # SAML identity provider (ref: x-pack/plugin/identity-provider)
        self.idp_service = None
        if bool(settings.get("xpack.idp.enabled", False)):
            from elasticsearch_tpu.xpack.saml import SamlIdentityProvider
            key_path = settings.get("xpack.idp.signing.key")
            cert_path = settings.get("xpack.idp.signing.certificate")
            if not (key_path and cert_path):
                raise ValueError(
                    "xpack.idp.enabled requires xpack.idp.signing.key "
                    "and xpack.idp.signing.certificate")
            with open(key_path, "rb") as fh:
                key_pem = fh.read()
            with open(cert_path) as fh:
                cert_pem = fh.read()
            self.idp_service = SamlIdentityProvider(
                str(settings.get("xpack.idp.entity_id", "")),
                key_pem, cert_pem,
                sso_url=str(settings.get("xpack.idp.sso_url", "")))
        from elasticsearch_tpu.xpack.sql import SqlService
        self.sql_service = SqlService(self)
        from elasticsearch_tpu.xpack.eql import EqlService
        self.eql_service = EqlService(self)
        from elasticsearch_tpu.xpack.ml import MlService
        self.ml_service = MlService(self)
        from elasticsearch_tpu.xpack.rollup import RollupService
        self.rollup_service = RollupService(self)
        from elasticsearch_tpu.xpack.enrich import EnrichService
        self.enrich_service = EnrichService(self)
        from elasticsearch_tpu.xpack.graph import GraphService
        self.graph_service = GraphService(self)
        from elasticsearch_tpu.xpack.watcher import WatcherService
        self.watcher_service = WatcherService(self)
        self.watcher_service.start_scheduler()
        from elasticsearch_tpu.xpack.monitoring import MonitoringService
        self.monitoring_service = MonitoringService(self)
        self.monitoring_service.start()
        from elasticsearch_tpu.transport.remote import RemoteClusterService
        self.remote_cluster_service = RemoteClusterService(self)
        # static cluster.remote.* settings connect at startup, same as
        # the dynamic _cluster/settings surface (ref:
        # RemoteClusterService#listenForUpdates + initial settings)
        try:
            self.remote_cluster_service.apply_settings(
                self.settings.as_dict())
        except Exception:
            logger.exception("initial remote-cluster settings invalid")
        # persistent cluster-settings overlay (the _cluster/settings API)
        self.persistent_settings = {}
        self.search_service.cluster_settings = lambda: self.persistent_settings
        from elasticsearch_tpu.xpack.ccr import CcrService
        self.ccr_service = CcrService(self)
        # processors that join against live services (enrich) resolve
        # the node through the ingest service
        self.ingest_service.node = self
        # per-request thread-local context (authenticated user)
        import threading
        self.request_context = threading.local()
        # the action seam: ActionType registry + in-process client (ref:
        # ActionModule.setupActions + NodeClient — REST handlers resolve
        # actions by name instead of reaching into services)
        from elasticsearch_tpu.action import register_core_actions
        self.client = register_core_actions(self)
        self.rest_controller = RestController(self)
        self._http: Optional[HttpServer] = None
        # plugin loading + wiring (ref: node/Node.java:318-320 —
        # PluginsService construction feeds every registry; REST routes
        # and start hooks attach once the controller exists)
        from elasticsearch_tpu.plugins import PluginsService
        plugin_dir = settings.get("path.plugins") or os.path.join(
            self.data_path, "plugins")
        self.plugins_service = PluginsService(plugin_dir)
        self.plugins_service.load_all()
        self.plugins_service.wire_node(self)

    def start(self, port: Optional[int] = None) -> int:
        """Bind HTTP; returns the bound port (0 → ephemeral)."""
        http_port = port if port is not None else HTTP_PORT_SETTING.get(self.settings)
        # bootstrap checks: loopback binds warn, non-loopback binds
        # enforce (ref: BootstrapChecks.check at Bootstrap.init)
        from elasticsearch_tpu.common.bootstrap import run_bootstrap_checks
        run_bootstrap_checks(self.settings,
                             str(self.settings.get("http.host",
                                                   "127.0.0.1")))
        ssl_config = None
        if self.settings.get("xpack.security.http.ssl.enabled"):
            # ref: xpack.security.http.ssl.* settings
            ssl_config = {
                "certificate": self.settings.get(
                    "xpack.security.http.ssl.certificate"),
                "key": self.settings.get("xpack.security.http.ssl.key"),
                "client_auth": self.settings.get(
                    "xpack.security.http.ssl.client_authentication",
                    "none"),
                "certificate_authorities": self.settings.get(
                    "xpack.security.http.ssl.certificate_authorities"),
            }
        # native epoll front (C++, rest/native_http.py) unless TLS is on
        # or the setting/toolchain says otherwise; falls back to the
        # stdlib server transparently. Settings parse FIRST so a typo
        # falls back instead of crashing a half-started front.
        native_pref = self.settings.get("http.native", "auto")
        allow = str(self.settings.get("http.ip_filter.allow", "") or "")
        deny = str(self.settings.get("http.ip_filter.deny", "") or "")
        # persistent compile cache for EVERY serving front (stdlib
        # included — the Python plan path compiles serving shapes too):
        # warm sessions deserialize executables instead of recompiling,
        # and GET /_kernels classifies warm loads as cache hits
        from elasticsearch_tpu.search.fastpath import enable_compile_cache
        enable_compile_cache()
        self._http = None
        if ssl_config is None and native_pref in ("auto", True, "true"):
            front = None
            try:
                nb_buckets = self.settings.get(
                    "http.native.fast_nb_buckets") or (1024, 2048, 4096)
                if isinstance(nb_buckets, str):
                    nb_buckets = tuple(
                        int(x) for x in nb_buckets.split(","))
                fast_streams = int(self.settings.get(
                    "http.native.fast_streams", 4))
                fast_max_k = int(self.settings.get(
                    "http.native.fast_max_k", 1000))
                from elasticsearch_tpu.rest.native_http import (
                    NativeHttpFront)
                front = NativeHttpFront.try_acquire(
                    self.rest_controller, metrics=self.telemetry.metrics)
                if front is not None:
                    front.start(http_port)
                    from elasticsearch_tpu.search.fastpath import (
                        FastPathServer)
                    front.fastpath = FastPathServer(
                        self, front, nb_buckets=nb_buckets,
                        n_streams=fast_streams, max_k=fast_max_k,
                        q_batch=int(self.settings.get(
                            "http.native.fast_q_batch", 32)),
                        # oversize queries: impact-ordered truncation
                        # ("certified" | "always" | "off")
                        impact_mode=str(self.settings.get(
                            "http.native.fast_impact", "certified")))
                    front.fastpath.start()
                    if allow or deny:
                        front.set_ipfilter(allow, deny)
                    self._http = front
            except Exception:
                logger.exception(
                    "native http front failed; using stdlib server")
                if front is not None:
                    try:
                        front.stop()
                    except Exception:
                        pass
                self._http = None
        if self._http is None:
            self._http = HttpServer(self.rest_controller, port=http_port,
                                    ssl_config=ssl_config,
                                    ip_filter=(allow, deny))
            self._http.start()
        # SQL line protocol for external drivers/CLI (ref: the JDBC/CLI
        # seam, x-pack/plugin/sql/jdbc + sql-cli) — opt-in via
        # xpack.sql.port (0 = ephemeral)
        sql_port = self.settings.get("xpack.sql.port")
        if sql_port is not None:
            from elasticsearch_tpu.xpack.sql_protocol import (
                SqlProtocolServer)
            self._sql_protocol = SqlProtocolServer(
                self.sql_service, port=int(sql_port),
                security_service=self.security_service)
        # sd_notify READY under systemd (ref: modules/systemd)
        from elasticsearch_tpu.common.systemd import notify_ready
        notify_ready()
        return self._http.port

    def stop(self):
        if getattr(self, "_sql_protocol", None) is not None:
            self._sql_protocol.close()
            self._sql_protocol = None
        if self._http is not None:
            from elasticsearch_tpu.common.systemd import notify_stopping
            notify_stopping()
            self._http.stop()
            self._http = None

    def close(self):
        self.stop()
        from elasticsearch_tpu.search import queries as _queries_mod
        if _queries_mod.STORED_SCRIPT_RESOLVER is getattr(
                self, "_stored_script_resolver", None):
            _queries_mod.STORED_SCRIPT_RESOLVER = None
        from elasticsearch_tpu.index import engine as _engine_mod
        _engine_mod.LAZY_MATERIALIZERS.pop(self.data_path, None)
        from elasticsearch_tpu.repositories import blobstore as _bs
        _bs.NODE_KEYSTORES.pop(self.data_path, None)
        self.threadpool.shutdown()
        self.watcher_service.stop()
        self.monitoring_service.stop()
        self.ccr_service.stop()
        self.persistent_tasks.stop_all()
        self.indices_service.close()
