"""REST API: route registry + dispatch.

Mirrors the reference's REST layer (ref: rest/RestController.java:62,146-174
— trie route dispatch; ~180 handlers under rest/action/; the _cat family
under rest/action/cat/). The controller is transport-agnostic — the HTTP
server (rest/http_server.py) adapts sockets to ``dispatch()``, the way
Netty4HttpServerTransport feeds RestController — so tests can drive the
full API without sockets (the YAML-rest-test model, SURVEY.md §4 tier 5).
"""

from __future__ import annotations

import json
import os
import re
import time
import uuid
from contextlib import ExitStack
from typing import Any, Callable, Dict, List, Optional, Tuple

from elasticsearch_tpu import __version__
from elasticsearch_tpu.common.errors import (
    DocumentMissingException,
    ElasticsearchTpuException,
    IllegalArgumentException,
    ParsingException,
    ResourceNotFoundException,
)
from elasticsearch_tpu.search.rank_eval import rank_eval
from elasticsearch_tpu.telemetry import context as _telectx
from elasticsearch_tpu.telemetry import flightrecorder as _flightrec
from elasticsearch_tpu.telemetry.tracing import host_span
from elasticsearch_tpu.transport.tasks import CancellableTask, TaskId

Response = Tuple[int, Dict[str, Any]]


class RestController:
    def __init__(self, node):
        self.node = node
        # (method, compiled-regex, param-names, handler)
        self._routes: List[Tuple[str, Any, List[str], Callable]] = []
        _register_all(self)

    def register(self, method: str, pattern: str, handler: Callable):
        """pattern like "/{index}/_doc/{id}" — path params in braces."""
        names = re.findall(r"{(\w+)}", pattern)
        # {index} must not swallow _endpoint paths (only _all is a valid
        # underscore-leading index expression, ref: RestController routing)
        regex_src = pattern.replace("{index}", "(?P<index>_all|[^_/][^/]*)")
        regex = re.compile(
            "^" + re.sub(r"{(\w+)}", r"(?P<\1>[^/]+)", regex_src) + "/?$")
        self._routes.append((method.upper(), regex, names, handler))

    def dispatch(self, method: str, path: str,
                 params: Optional[Dict[str, str]] = None,
                 body: Any = None,
                 headers: Optional[Dict[str, str]] = None) -> Response:
        params = params or {}
        method = method.upper()
        path = path.rstrip("/") or "/"
        sec = getattr(self.node, "security_service", None)
        self.node.request_context.user = None
        # SSO login endpoints authenticate by their OWN payload (the
        # IdP's signed response / the token being invalidated), never by
        # request headers (ref: RestSamlAuthenticateAction et al. are
        # exempt from the authentication filter)
        auth_exempt = path in (
            "/_security/saml/prepare", "/_security/saml/authenticate",
            "/_security/saml/logout")
        if sec is not None and sec.enabled and not auth_exempt:
            from elasticsearch_tpu.xpack.security import required_privilege
            try:
                user = sec.authenticate(headers)
            except ElasticsearchTpuException as e:
                sec.audit.authentication_failed(method, path, str(e))
                # authentication challenges (ref: the reference's 401s
                # carry WWW-Authenticate for every enabled scheme, incl.
                # Negotiate when a Kerberos realm is configured —
                # standards SPNEGO clients won't send a token unsolicited)
                challenges = ['Basic realm="security" charset="UTF-8"',
                              "Bearer realm=\"security\"",
                              "ApiKey"]
                if any(r.type == "kerberos" for r in sec.realms):
                    challenges.insert(0, "Negotiate")
                return e.status, {
                    "error": {**e.to_xcontent(),
                              "root_cause": [e.to_xcontent()]},
                    "status": e.status,
                    "_headers": {"WWW-Authenticate": ", ".join(challenges)},
                }
            sec.audit.authentication_success(
                user, user.authenticated_realm or "__anonymous__",
                method, path)
            kind, priv, index = required_privilege(method, path)
            if priv != "none":
                try:
                    sec.authorize(user, kind, priv, index)
                except ElasticsearchTpuException as e:
                    sec.audit.access_denied(user, priv, method, path)
                    return e.status, {
                        "error": {**e.to_xcontent(),
                                  "root_cause": [e.to_xcontent()]},
                        "status": e.status,
                    }
                sec.audit.access_granted(user, priv, method, path)
            self.node.request_context.user = user
        # client attribution + launch provenance: X-Opaque-Id (case-
        # insensitive, ref: Task.X_OPAQUE_ID) becomes ambient for the
        # handler, and the node's flight recorder is armed so every
        # kernel launch / device readback under this request lands in
        # the ring tagged with the request's trace
        opaque = next((str(v) for k, v in (headers or {}).items()
                       if k.lower() == "x-opaque-id"), None)
        # tenant attribution: X-Tenant-Id header is the strongest tag
        # (precedence: header > request body > index.tenant.default);
        # becomes ambient so every phase under this request charges the
        # right tenant's accounting row
        tenant = next((str(v) for k, v in (headers or {}).items()
                       if k.lower() == "x-tenant-id"), None)
        # workload-class attribution: X-Workload-Class is the strongest
        # tag (precedence: header > request shape classification)
        workload = next((str(v) for k, v in (headers or {}).items()
                         if k.lower() == "x-workload-class"), None)
        flight = getattr(getattr(self.node, "telemetry", None),
                         "flight", None)
        matched_path = False
        for m, regex, names, handler in self._routes:
            match = regex.match(path)
            if match is None:
                continue
            matched_path = True
            if m != method and not (m == "GET" and method == "HEAD"):
                continue
            try:
                kwargs = match.groupdict()
                with ExitStack() as stack:
                    if opaque:
                        stack.enter_context(
                            _telectx.activate_opaque(opaque))
                    if tenant:
                        stack.enter_context(
                            _telectx.activate_tenant(tenant))
                    if workload:
                        stack.enter_context(
                            _telectx.activate_workload_class(workload))
                    if flight is not None:
                        stack.enter_context(_flightrec.activate(flight))
                    return handler(self.node, params, body, **kwargs)
            except ElasticsearchTpuException as e:
                return e.status, {
                    "error": {**e.to_xcontent(),
                              "root_cause": [e.to_xcontent()]},
                    "status": e.status,
                }
            except Exception as e:        # noqa: BLE001
                # unexpected failures become 500 responses, never dropped
                # connections (ref: RestController catches Throwable and
                # answers with an error body)
                import logging
                import traceback
                logging.getLogger("rest.controller").error(
                    "unhandled error for %s %s\n%s", method, path,
                    traceback.format_exc())
                name = type(e).__name__
                snake = "".join(
                    ("_" + ch.lower()) if ch.isupper() and i > 0
                    else ch.lower() for i, ch in enumerate(name))
                err = {"type": snake, "reason": str(e)}
                return 500, {"error": {**err, "root_cause": [err]},
                             "status": 500}
        if matched_path:
            return 405, {"error": f"Incorrect HTTP method for uri [{path}], "
                                  f"allowed: {self._allowed(path)}", "status": 405}
        return 400, {"error": {"type": "illegal_argument_exception",
                               "reason": f"no handler found for uri [{path}] "
                                         f"and method [{method}]"},
                     "status": 400}

    def _allowed(self, path: str) -> List[str]:
        return sorted({m for m, regex, _, _ in self._routes
                       if regex.match(path)})


# ---------------------------------------------------------------------------
# handlers (ref: the RestHandler classes under rest/action/)
# ---------------------------------------------------------------------------

def _register_all(c: RestController):
    c.register("GET", "/", root_info)
    # cluster/admin
    c.register("GET", "/_cluster/health", cluster_health)
    c.register("GET", "/_health_report", health_report)
    c.register("GET", "/_health_report/{indicator}", health_report)
    c.register("GET", "/_tenants/stats", tenants_stats)
    c.register("GET", "/_workload/stats", workload_stats)
    c.register("GET", "/_cluster/pending_tasks", cluster_pending_tasks)
    c.register("GET", "/_cluster/stats", cluster_stats)
    c.register("GET", "/_nodes/stats", nodes_stats)
    # recent-trace surface (telemetry/): span ring buffer + span trees
    c.register("GET", "/_traces", get_traces)
    c.register("GET", "/_traces/{trace_id}", get_trace)
    c.register("GET", "/_flight_recorder", get_flight_recorder)
    c.register("GET", "/_flight_recorder/waterfall/{trace_id}",
               get_flight_waterfall)
    # engine observability (telemetry/engine.py): per-kernel compile table
    c.register("GET", "/_kernels", get_kernels)
    c.register("GET", "/_cat/indices", cat_indices)
    c.register("GET", "/_cat/health", cat_health)
    c.register("GET", "/_cat/tenants", cat_tenants)
    c.register("GET", "/_cat/workload", cat_workload)
    c.register("GET", "/_cat/count", cat_count)
    c.register("GET", "/_cat/shards", cat_shards)
    c.register("GET", "/_stats", indices_stats)
    # search (register before index-level wildcards)
    c.register("GET", "/_search", search_all)
    c.register("POST", "/_search", search_all)
    c.register("POST", "/_search/scroll", scroll)
    c.register("GET", "/_search/scroll", scroll)
    c.register("DELETE", "/_search/scroll", clear_scroll)
    c.register("POST", "/_msearch", msearch)
    c.register("GET", "/_mget", mget_all)
    c.register("POST", "/_mget", mget_all)
    c.register("POST", "/_bulk", bulk)
    c.register("PUT", "/_bulk", bulk)
    c.register("GET", "/{index}/_search", search_index)
    c.register("POST", "/{index}/_search", search_index)
    c.register("GET", "/{index}/_count", count_index)
    c.register("POST", "/{index}/_count", count_index)
    c.register("POST", "/{index}/_msearch", msearch_index)
    c.register("POST", "/{index}/_rank_eval", rank_eval_handler)
    c.register("GET", "/{index}/_rank_eval", rank_eval_handler)
    c.register("GET", "/{index}/_explain/{id}", explain_doc)
    c.register("POST", "/{index}/_explain/{id}", explain_doc)
    # search utility APIs
    c.register("GET", "/_field_caps", field_caps)
    c.register("POST", "/_field_caps", field_caps)
    c.register("GET", "/{index}/_field_caps", field_caps)
    c.register("POST", "/{index}/_field_caps", field_caps)
    c.register("GET", "/{index}/_validate/query", validate_query)
    c.register("POST", "/{index}/_validate/query", validate_query)
    c.register("POST", "/{index}/_terms_enum", terms_enum)
    c.register("GET", "/{index}/_terms_enum", terms_enum)
    c.register("GET", "/_resolve/index/{expression}", resolve_index)
    c.register("POST", "/{index}/_pit", open_pit)
    c.register("DELETE", "/_pit", close_pit)
    # stored scripts + search templates
    c.register("PUT", "/_scripts/{id}", put_stored_script)
    c.register("POST", "/_scripts/{id}", put_stored_script)
    c.register("GET", "/_scripts/{id}", get_stored_script)
    c.register("DELETE", "/_scripts/{id}", delete_stored_script)
    c.register("POST", "/_render/template", render_search_template)
    c.register("GET", "/_render/template", render_search_template)
    c.register("POST", "/_render/template/{id}", render_search_template)
    c.register("POST", "/_search/template", search_template_all)
    c.register("GET", "/_search/template", search_template_all)
    c.register("POST", "/{index}/_search/template", search_template)
    c.register("GET", "/{index}/_search/template", search_template)
    c.register("POST", "/_msearch/template", msearch_template)
    c.register("POST", "/{index}/_msearch/template", msearch_template)
    # reindex family (ref: modules/reindex)
    c.register("POST", "/_reindex", reindex_handler)
    c.register("POST", "/{index}/_update_by_query", update_by_query_handler)
    c.register("POST", "/{index}/_delete_by_query", delete_by_query_handler)
    c.register("POST", "/_reindex/{task_id}/_rethrottle", rethrottle_handler)
    c.register("POST", "/_update_by_query/{task_id}/_rethrottle",
               rethrottle_handler)
    c.register("POST", "/_delete_by_query/{task_id}/_rethrottle",
               rethrottle_handler)
    # tasks
    c.register("GET", "/_tasks", list_tasks)
    c.register("POST", "/_tasks/_cancel", cancel_tasks)
    c.register("GET", "/_tasks/{task_id}", get_task)
    c.register("POST", "/_tasks/{task_id}/_cancel", cancel_task)
    # async search
    c.register("POST", "/_async_search", submit_async_search)
    c.register("GET", "/_async_search/{id}", get_async_search)
    c.register("DELETE", "/_async_search/{id}", delete_async_search)
    c.register("POST", "/{index}/_async_search", submit_async_search)
    # aliases
    c.register("POST", "/_aliases", update_aliases)
    c.register("GET", "/_alias", get_alias)
    c.register("GET", "/_alias/{name}", get_alias)
    c.register("GET", "/_cat/aliases", cat_aliases)
    c.register("PUT", "/{index}/_alias/{name}", put_alias)
    c.register("POST", "/{index}/_alias/{name}", put_alias)
    c.register("PUT", "/{index}/_aliases/{name}", put_alias)
    c.register("DELETE", "/{index}/_alias/{name}", delete_alias)
    c.register("DELETE", "/{index}/_aliases/{name}", delete_alias)
    c.register("GET", "/{index}/_alias", get_alias)
    c.register("GET", "/{index}/_alias/{name}", get_alias)
    # templates
    c.register("PUT", "/{index}/_block/{block}", add_index_block)
    c.register("PUT", "/_index_template/{name}", put_index_template)
    c.register("POST", "/_index_template/{name}", put_index_template)
    c.register("GET", "/_index_template", get_index_template)
    c.register("GET", "/_index_template/{name}", get_index_template)
    c.register("DELETE", "/_index_template/{name}", delete_index_template)
    c.register("PUT", "/_component_template/{name}", put_component_template)
    c.register("GET", "/_component_template", get_component_template)
    c.register("GET", "/_component_template/{name}", get_component_template)
    c.register("DELETE", "/_component_template/{name}",
               delete_component_template)
    # rollover / resize
    c.register("POST", "/{index}/_rollover", rollover_index)
    c.register("POST", "/{index}/_rollover/{new_index}", rollover_index)
    c.register("PUT", "/{index}/_shrink/{target}", shrink_index)
    c.register("POST", "/{index}/_shrink/{target}", shrink_index)
    c.register("PUT", "/{index}/_split/{target}", split_index)
    c.register("POST", "/{index}/_split/{target}", split_index)
    c.register("PUT", "/{index}/_clone/{target}", clone_index)
    c.register("POST", "/{index}/_clone/{target}", clone_index)
    # data streams
    c.register("PUT", "/_data_stream/{name}", create_data_stream)
    c.register("GET", "/_data_stream", get_data_stream)
    c.register("GET", "/_data_stream/{name}", get_data_stream)
    c.register("DELETE", "/_data_stream/{name}", delete_data_stream)
    # snapshots
    c.register("PUT", "/_snapshot/{repo}", put_repository)
    c.register("POST", "/_snapshot/{repo}", put_repository)
    c.register("GET", "/_snapshot/{repo}", get_repository)
    c.register("GET", "/_snapshot", get_repository)
    c.register("DELETE", "/_snapshot/{repo}", delete_repository)
    c.register("PUT", "/_snapshot/{repo}/{snap}", create_snapshot)
    c.register("POST", "/_snapshot/{repo}/{snap}", create_snapshot)
    c.register("GET", "/_snapshot/{repo}/{snap}/_status", snapshot_status)
    c.register("GET", "/_snapshot/{repo}/{snap}", get_snapshot)
    c.register("DELETE", "/_snapshot/{repo}/{snap}", delete_snapshot)
    c.register("POST", "/_snapshot/{repo}/{snap}/_restore", restore_snapshot)
    # transform
    # index state: open/close, freeze/unfreeze (ref:
    # MetadataIndexStateService; x-pack frozen-indices)
    c.register("POST", "/{index}/_close", close_index)
    c.register("POST", "/{index}/_open", open_index)
    c.register("POST", "/{index}/_freeze", freeze_index)
    c.register("POST", "/{index}/_unfreeze", unfreeze_index)
    # searchable snapshots (ref: x-pack searchable-snapshots)
    c.register("POST", "/_snapshot/{repo}/{snap}/_mount", mount_snapshot)
    c.register("GET", "/_searchable_snapshots/stats",
               searchable_snapshot_stats)
    # nodes diagnostics + deprecation + autoscaling
    c.register("GET", "/_nodes", nodes_info)
    c.register("GET", "/_xpack", xpack_info)
    c.register("GET", "/_license", license_info)
    c.register("GET", "/_nodes/hot_threads", hot_threads)
    c.register("POST", "/_cluster/voting_config_exclusions",
               add_voting_exclusions)
    c.register("DELETE", "/_cluster/voting_config_exclusions",
               clear_voting_exclusions)
    c.register("GET", "/_cluster/allocation/explain", allocation_explain)
    c.register("POST", "/_cluster/allocation/explain", allocation_explain)
    c.register("POST", "/_nodes/reload_secure_settings",
               reload_secure_settings)
    c.register("GET", "/_migration/deprecations", deprecations)
    c.register("PUT", "/_autoscaling/policy/{name}", autoscaling_put)
    c.register("GET", "/_autoscaling/policy/{name}", autoscaling_get)
    c.register("DELETE", "/_autoscaling/policy/{name}",
               autoscaling_delete)
    c.register("GET", "/_autoscaling/capacity", autoscaling_capacity)
    # rolling upgrades: node-shutdown markers (ref: x-pack shutdown)
    c.register("GET", "/_nodes/shutdown", get_all_node_shutdowns)
    c.register("PUT", "/_nodes/{node_id}/shutdown", put_node_shutdown)
    c.register("GET", "/_nodes/{node_id}/shutdown", get_node_shutdown)
    c.register("DELETE", "/_nodes/{node_id}/shutdown",
               delete_node_shutdown)
    # extended _cat family (ref: rest/action/cat/)
    c.register("GET", "/_cat/nodes", cat_nodes)
    c.register("GET", "/_cat/plugins", cat_plugins)
    c.register("GET", "/_cat/master", cat_master)
    c.register("GET", "/_cat/snapshots/{repo}", cat_snapshots)
    c.register("GET", "/_cat/fielddata", cat_fielddata)
    c.register("GET", "/_cat/ml/anomaly_detectors", cat_ml_jobs)
    c.register("GET", "/_cat/ml/datafeeds", cat_ml_datafeeds)
    c.register("GET", "/_cat/ml/trained_models", cat_ml_trained_models)
    c.register("GET", "/_cat/transforms", cat_transforms)
    c.register("GET", "/_cat/allocation", cat_allocation)
    c.register("GET", "/_cat/templates", cat_templates)
    c.register("GET", "/_cat/thread_pool", cat_thread_pool)
    c.register("GET", "/_cat/pending_tasks", cat_pending_tasks)
    c.register("GET", "/_cat/segments", cat_segments)
    c.register("GET", "/_cat/recovery", cat_recovery)
    c.register("GET", "/_cat/repositories", cat_repositories)
    c.register("GET", "/_cat/snapshots/{repo}", cat_snapshots)
    c.register("GET", "/_cat/tasks", cat_tasks)
    c.register("GET", "/_cat/nodeattrs", cat_nodeattrs)
    # cluster settings + remote clusters (ref: RemoteClusterService)
    c.register("PUT", "/_cluster/settings", put_cluster_settings)
    c.register("GET", "/_cluster/settings", get_cluster_settings)
    # allocation commands + recovery progress (ref: RestRerouteAction,
    # RestRecoveryAction; the multi-node forms live on the cluster
    # client — this is the single-node surface's honest rendering)
    c.register("POST", "/_cluster/reroute", cluster_reroute)
    c.register("GET", "/_recovery", indices_recovery)
    c.register("GET", "/{index}/_recovery", index_recovery)
    c.register("GET", "/_remote/info", remote_info)
    # watcher (ref: x-pack/plugin/watcher REST layer)
    c.register("PUT", "/_watcher/watch/{id}", watcher_put)
    c.register("POST", "/_watcher/watch/{id}", watcher_put)
    c.register("GET", "/_watcher/watch/{id}", watcher_get)
    c.register("DELETE", "/_watcher/watch/{id}", watcher_delete)
    c.register("POST", "/_watcher/watch/{id}/_execute", watcher_execute)
    c.register("PUT", "/_watcher/watch/{id}/_activate", watcher_activate)
    c.register("POST", "/_watcher/watch/{id}/_activate",
               watcher_activate)
    c.register("PUT", "/_watcher/watch/{id}/_deactivate",
               watcher_deactivate)
    c.register("POST", "/_watcher/watch/{id}/_deactivate",
               watcher_deactivate)
    c.register("GET", "/_watcher/stats", watcher_stats)
    # monitoring (ref: x-pack/plugin/monitoring REST layer)
    c.register("POST", "/_monitoring/bulk", monitoring_bulk)
    c.register("POST", "/_monitoring/_collect", monitoring_collect)
    # CCR (ref: x-pack/plugin/ccr REST layer)
    c.register("PUT", "/{index}/_ccr/follow", ccr_follow)
    c.register("POST", "/{index}/_ccr/pause_follow", ccr_pause)
    c.register("POST", "/{index}/_ccr/resume_follow", ccr_resume)
    c.register("POST", "/{index}/_ccr/unfollow", ccr_unfollow)
    c.register("GET", "/{index}/_ccr/info", ccr_info)
    c.register("GET", "/_ccr/stats", ccr_stats)
    c.register("POST", "/{index}/_ccr/changes", ccr_changes)
    c.register("PUT", "/_ccr/auto_follow/{name}", ccr_put_auto_follow)
    c.register("GET", "/_ccr/auto_follow/{name}", ccr_get_auto_follow)
    c.register("GET", "/_ccr/auto_follow", ccr_get_auto_follow_all)
    c.register("DELETE", "/_ccr/auto_follow/{name}",
               ccr_delete_auto_follow)
    # rollup (ref: x-pack/plugin/rollup REST layer)
    c.register("PUT", "/_rollup/job/{id}", rollup_put_job)
    c.register("GET", "/_rollup/job/{id}", rollup_get_job)
    c.register("DELETE", "/_rollup/job/{id}", rollup_delete_job)
    c.register("POST", "/_rollup/job/{id}/_start", rollup_start_job)
    c.register("POST", "/_rollup/job/{id}/_stop", rollup_stop_job)
    c.register("GET", "/_rollup/data/{id}", rollup_caps)
    c.register("POST", "/{index}/_rollup_search", rollup_search)
    c.register("GET", "/{index}/_rollup_search", rollup_search)
    # enrich (ref: x-pack/plugin/enrich REST layer)
    c.register("PUT", "/_enrich/policy/{name}", enrich_put_policy)
    c.register("GET", "/_enrich/policy/{name}", enrich_get_policy)
    c.register("GET", "/_enrich/policy", enrich_list_policies)
    c.register("DELETE", "/_enrich/policy/{name}", enrich_delete_policy)
    c.register("POST", "/_enrich/policy/{name}/_execute",
               enrich_execute_policy)
    # graph (ref: x-pack/plugin/graph REST layer)
    c.register("POST", "/{index}/_graph/explore", graph_explore)
    c.register("GET", "/{index}/_graph/explore", graph_explore)
    # ML (ref: x-pack/plugin/ml REST layer)
    c.register("PUT", "/_ml/anomaly_detectors/{id}", ml_put_job)
    c.register("GET", "/_ml/anomaly_detectors/{id}", ml_get_job)
    c.register("GET", "/_ml/anomaly_detectors", ml_get_jobs)
    c.register("DELETE", "/_ml/anomaly_detectors/{id}", ml_delete_job)
    c.register("POST", "/_ml/anomaly_detectors/{id}/_open", ml_open_job)
    c.register("POST", "/_ml/anomaly_detectors/{id}/_close", ml_close_job)
    c.register("GET", "/_ml/anomaly_detectors/{id}/model_snapshots",
               ml_model_snapshots)
    c.register("POST",
               "/_ml/anomaly_detectors/{id}/model_snapshots/{sid}/_revert",
               ml_revert_snapshot)
    c.register("POST", "/_ml/anomaly_detectors/{id}/_data", ml_post_data)
    c.register("GET", "/_ml/anomaly_detectors/{id}/results/buckets",
               ml_get_buckets)
    c.register("POST", "/_ml/anomaly_detectors/{id}/results/buckets",
               ml_get_buckets)
    c.register("GET", "/_ml/anomaly_detectors/{id}/results/records",
               ml_get_records)
    c.register("POST", "/_ml/anomaly_detectors/{id}/results/records",
               ml_get_records)
    c.register("PUT", "/_ml/datafeeds/{id}", ml_put_datafeed)
    c.register("GET", "/_ml/datafeeds/{id}", ml_get_datafeed)
    c.register("DELETE", "/_ml/datafeeds/{id}", ml_delete_datafeed)
    c.register("POST", "/_ml/datafeeds/{id}/_start", ml_start_datafeed)
    c.register("POST", "/_ml/datafeeds/{id}/_stop", ml_stop_datafeed)
    c.register("PUT", "/_ml/data_frame/analytics/{id}", ml_put_analytics)
    c.register("GET", "/_ml/data_frame/analytics/{id}", ml_get_analytics)
    c.register("POST", "/_ml/data_frame/analytics/{id}/_start",
               ml_start_analytics)
    c.register("PUT", "/_ml/trained_models/{id}", ml_put_model)
    c.register("GET", "/_ml/trained_models/{id}", ml_get_model)
    c.register("DELETE", "/_ml/trained_models/{id}", ml_delete_model)
    c.register("POST", "/_ml/trained_models/{id}/_infer", ml_infer)
    c.register("POST", "/_ml/trained_models/{id}/deployment/_infer",
               ml_infer)
    # EQL (ref: x-pack/plugin/eql REST layer)
    c.register("POST", "/{index}/_eql/search", eql_search)
    c.register("GET", "/{index}/_eql/search", eql_search)
    # SQL (ref: x-pack/plugin/sql REST layer)
    c.register("POST", "/_sql", sql_query)
    c.register("GET", "/_sql", sql_query)
    c.register("POST", "/_sql/translate", sql_translate)
    c.register("GET", "/_sql/translate", sql_translate)
    c.register("POST", "/_sql/close", sql_close)
    c.register("PUT", "/_transform/{id}", transform_put)
    c.register("GET", "/_transform/{id}", transform_get)
    c.register("GET", "/_transform", transform_get)
    c.register("DELETE", "/_transform/{id}", transform_delete)
    c.register("POST", "/_transform/_preview", transform_preview)
    c.register("POST", "/_transform/{id}/_start", transform_start)
    c.register("POST", "/_transform/{id}/_stop", transform_stop)
    c.register("GET", "/_transform/{id}/_stats", transform_stats)
    c.register("POST", "/_transform/{id}/_schedule_now", transform_schedule_now)
    # security
    c.register("GET", "/_security/_authenticate", security_authenticate)
    c.register("PUT", "/_security/user/{name}", security_put_user)
    c.register("POST", "/_security/user/{name}", security_put_user)
    c.register("GET", "/_security/user/{name}", security_get_user)
    c.register("GET", "/_security/user", security_get_user)
    c.register("DELETE", "/_security/user/{name}", security_delete_user)
    c.register("PUT", "/_security/user/{name}/_password", security_change_password)
    c.register("POST", "/_security/user/{name}/_password", security_change_password)
    c.register("PUT", "/_security/role/{name}", security_put_role)
    c.register("POST", "/_security/role/{name}", security_put_role)
    c.register("GET", "/_security/role/{name}", security_get_role)
    c.register("GET", "/_security/role", security_get_role)
    c.register("DELETE", "/_security/role/{name}", security_delete_role)
    c.register("POST", "/_security/api_key", security_create_api_key)
    c.register("GET", "/_security/privilege/_builtin",
               security_builtin_privileges)
    c.register("PUT", "/_security/api_key", security_create_api_key)
    c.register("GET", "/_security/api_key", security_get_api_keys)
    c.register("DELETE", "/_security/api_key", security_invalidate_api_key)
    c.register("POST", "/_security/oauth2/token", security_create_token)
    c.register("DELETE", "/_security/oauth2/token",
               security_invalidate_token)
    c.register("POST", "/_security/delegate_pki", security_delegate_pki)
    c.register("PUT", "/_idp/saml/sp/{sp_entity_id}", idp_put_sp)
    c.register("DELETE", "/_idp/saml/sp/{sp_entity_id}", idp_delete_sp)
    c.register("GET", "/_idp/saml/metadata/{sp_entity_id}", idp_metadata)
    c.register("POST", "/_idp/saml/validate", idp_validate)
    c.register("POST", "/_idp/saml/init", idp_init)
    c.register("POST", "/_security/saml/prepare", security_saml_prepare)
    c.register("POST", "/_security/saml/authenticate",
               security_saml_authenticate)
    c.register("POST", "/_security/saml/logout", security_saml_logout)
    c.register("PUT", "/_security/role_mapping/{name}",
               security_put_role_mapping)
    c.register("POST", "/_security/role_mapping/{name}",
               security_put_role_mapping)
    c.register("GET", "/_security/role_mapping/{name}",
               security_get_role_mapping)
    c.register("GET", "/_security/role_mapping",
               security_get_role_mapping)
    c.register("DELETE", "/_security/role_mapping/{name}",
               security_delete_role_mapping)
    # ilm
    c.register("PUT", "/_ilm/policy/{id}", ilm_put_policy)
    c.register("GET", "/_ilm/policy/{id}", ilm_get_policy)
    c.register("GET", "/_ilm/policy", ilm_get_policy)
    c.register("DELETE", "/_ilm/policy/{id}", ilm_delete_policy)
    c.register("GET", "/_ilm/status", ilm_status)
    c.register("POST", "/_ilm/start", ilm_start)
    c.register("POST", "/_ilm/stop", ilm_stop)
    c.register("GET", "/{index}/_ilm/explain", ilm_explain)
    c.register("POST", "/{index}/_ilm/remove", ilm_remove)
    c.register("POST", "/{index}/_ilm/retry", ilm_retry)
    c.register("PUT", "/{index}/_settings", put_settings)
    # slm
    c.register("PUT", "/_slm/policy/{id}", slm_put_policy)
    c.register("GET", "/_slm/policy/{id}", slm_get_policy)
    c.register("GET", "/_slm/policy", slm_get_policy)
    c.register("DELETE", "/_slm/policy/{id}", slm_delete_policy)
    c.register("POST", "/_slm/policy/{id}/_execute", slm_execute_policy)
    # ingest (literal _simulate before the {id} wildcard)
    c.register("POST", "/_ingest/pipeline/_simulate", simulate_pipeline)
    c.register("GET", "/_ingest/pipeline/_simulate", simulate_pipeline)
    c.register("POST", "/_ingest/pipeline/{id}/_simulate", simulate_pipeline)
    c.register("GET", "/_ingest/pipeline/{id}/_simulate", simulate_pipeline)
    c.register("PUT", "/_ingest/pipeline/{id}", put_pipeline)
    c.register("GET", "/_ingest/pipeline/{id}", get_pipeline)
    c.register("GET", "/_ingest/pipeline", get_pipelines)
    c.register("DELETE", "/_ingest/pipeline/{id}", delete_pipeline)
    # documents
    c.register("PUT", "/{index}/_doc/{id}", index_doc)
    c.register("POST", "/{index}/_doc/{id}", index_doc)
    c.register("POST", "/{index}/_doc", index_doc_auto_id)
    c.register("PUT", "/{index}/_create/{id}", create_doc)
    c.register("POST", "/{index}/_create/{id}", create_doc)
    c.register("GET", "/{index}/_doc/{id}", get_doc)
    c.register("GET", "/{index}/_termvectors/{id}", termvectors)
    c.register("POST", "/{index}/_termvectors/{id}", termvectors)
    c.register("POST", "/{index}/_mtermvectors", mtermvectors)
    c.register("GET", "/{index}/_mtermvectors", mtermvectors)
    c.register("DELETE", "/{index}/_doc/{id}", delete_doc)
    c.register("GET", "/{index}/_source/{id}", get_source)
    c.register("POST", "/{index}/_update/{id}", update_doc)
    c.register("POST", "/{index}/_bulk", bulk_index)
    c.register("PUT", "/{index}/_bulk", bulk_index)
    c.register("POST", "/{index}/_mget", mget_index)
    c.register("GET", "/{index}/_mget", mget_index)
    # index admin
    c.register("PUT", "/{index}", create_index)
    c.register("DELETE", "/{index}", delete_index)
    c.register("GET", "/{index}", get_index)
    c.register("GET", "/{index}/_mapping", get_mapping)
    c.register("PUT", "/{index}/_mapping", put_mapping)
    c.register("GET", "/{index}/_settings", get_settings)
    c.register("POST", "/{index}/_refresh", refresh_index)
    c.register("GET", "/{index}/_refresh", refresh_index)
    c.register("POST", "/{index}/_flush", flush_index)
    c.register("POST", "/{index}/_forcemerge", forcemerge_index)
    c.register("GET", "/{index}/_stats", index_stats)
    c.register("GET", "/{index}/_analyze", analyze)
    c.register("POST", "/{index}/_analyze", analyze)
    c.register("GET", "/_analyze", analyze_no_index)
    c.register("POST", "/_analyze", analyze_no_index)


# -- info / cluster ----------------------------------------------------------

def root_info(node, params, body):
    return 200, {
        "name": node.name,
        "cluster_name": node.cluster_name,
        "version": {"number": __version__,
                    "distribution": "elasticsearch_tpu"},
        "tagline": "You Know, for TPU Search",
    }


def _pending_cluster_tasks(node):
    """Pending cluster-state updates: the master-service queue when a
    coordinator is attached (multi-node), else the synchronous
    single-node container's — empty by construction — queue."""
    coord = getattr(node, "coordinator", None)
    if coord is not None:
        return coord.pending_task_summaries()
    return []


def cluster_health(node, params, body):
    # status comes from the ONE shard-availability implementation the
    # shards_availability health indicator also renders
    # (health/indicators.py shard_availability_summary) — the two
    # surfaces cannot drift
    from elasticsearch_tpu.health import shard_availability_summary
    coord = getattr(node, "coordinator", None)
    state = coord.applied_state if coord is not None else None
    summary = shard_availability_summary(state)
    if state is None:
        # single-process node: every shard is local and open — started
        # by construction
        shards = sum(idx.num_shards
                     for idx in node.indices_service.indices.values())
        summary["active_primary_shards"] = shards
        summary["active_shards"] = shards
    total = (summary["active_shards"] + summary["unassigned_shards"]
             + summary["initializing_shards"])
    pct = (100.0 * summary["active_shards"] / total) if total else 100.0
    return 200, {
        "cluster_name": node.cluster_name,
        "status": summary["status"],
        "timed_out": False,
        "number_of_nodes": 1,
        "number_of_data_nodes": 1,
        "active_primary_shards": summary["active_primary_shards"],
        "active_shards": summary["active_shards"],
        "relocating_shards": summary["relocating_shards"],
        "initializing_shards": summary["initializing_shards"],
        "unassigned_shards": summary["unassigned_shards"],
        "delayed_unassigned_shards": 0,
        # real numbers: the master-service queue + live fetch-phase
        # tasks from the task manager (no more hardcoded zeros)
        "number_of_pending_tasks": len(_pending_cluster_tasks(node)),
        "number_of_in_flight_fetch": len(
            node.task_manager.list_tasks(actions="*phase/fetch*")),
        "active_shards_percent_as_number": pct,
    }


def health_report(node, params, body, indicator=None):
    """GET /_health_report[/{indicator}] — the indicator catalog's
    verdicts (health/). Single-process: one node's local report in the
    cluster-report shape (details nested per node), so tooling written
    against the fan-out surface reads both."""
    from elasticsearch_tpu.health import (
        UnknownIndicatorError, merge_node_reports)
    try:
        local = node.health.local_report(indicator)
    except UnknownIndicatorError:
        return 400, {"error": {
            "type": "illegal_argument_exception",
            "reason": f"unknown health indicator [{indicator}]; one of "
                      f"{node.health.indicator_names()}"}}
    report = merge_node_reports({node.node_id: local})
    report["cluster_name"] = node.cluster_name
    return 200, report


def tenants_stats(node, params, body):
    """GET /_tenants/stats — per-tenant accounting (telemetry/tenants.py).
    Single-process: the local table rendered through the same merge the
    cluster fan-out uses, so both surfaces share one shape."""
    from elasticsearch_tpu.telemetry.tenants import merge_tenant_stats
    merged = merge_tenant_stats(
        {node.node_id: node.telemetry.tenants.stats()})
    merged["cluster_name"] = node.cluster_name
    return 200, merged


def workload_stats(node, params, body):
    """GET /_workload/stats — per-class accounting
    (telemetry/workload.py). Single-process: the local table rendered
    through the same merge the cluster fan-out uses."""
    from elasticsearch_tpu.telemetry.workload import merge_workload_stats
    merged = merge_workload_stats(
        {node.node_id: node.telemetry.workload.stats()})
    merged["cluster_name"] = node.cluster_name
    return 200, merged


def cluster_stats(node, params, body):
    indices = node.indices_service.indices
    docs = sum(idx.stats()["docs"]["count"] for idx in indices.values())
    return 200, {
        "cluster_name": node.cluster_name,
        "indices": {"count": len(indices), "docs": {"count": docs}},
        "nodes": {"count": {"total": 1, "data": 1, "master": 1}},
    }


def nodes_stats(node, params, body):
    import resource
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return 200, {
        "cluster_name": node.cluster_name,
        "nodes": {node.node_id: {
            "name": node.name,
            "indices": {
                name: idx.stats() for name, idx in
                node.indices_service.indices.items()},
            "request_cache": node.search_service.request_cache_stats,
            "process": {"max_rss_bytes": ru.ru_maxrss * 1024},
            # real numbers now: transport inbound charges
            # in_flight_requests, host readbacks charge request, device
            # admission charges hbm (utils/breaker.py live-path wiring)
            "breakers": node.breaker_service.stats(),
            # in-flight indexing bytes + per-stage rejection counters
            # (index/pressure.py — the write-path backpressure surface)
            "indexing_pressure": node.indexing_pressure.stats(),
            # named executors incl. the search pool's EWMA task time —
            # the signal adaptive replica selection consumes (ref:
            # ThreadPool stats / ResponseCollectorService)
            "thread_pool": node.threadpool.stats(),
            # metrics registry + trace store (telemetry/): counters,
            # gauges, latency histograms, recent slowlog entries;
            # ?history=true appends the windowed time-series ring view
            # (telemetry/history.py) — rates/deltas, not raw counters
            "telemetry": {
                **node.telemetry.to_dict(
                    history=params.get("history") == "true",
                    history_window=(float(params["history_window"])
                                    if params.get("history_window")
                                    else None)),
                "slowlog_recent":
                    list(node.search_service.slowlog_recent)[-16:],
            },
            # engine-level device stats: compile tracker rollup, HBM
            # bytes per slab class with peak watermark, device-cache
            # hit/miss/eviction counters (the TPU-native analogue of
            # segment stats + IndicesQueryCache + fielddata memory)
            "engine": _engine_section(node),
            # live/peak/lifetime task counts (transport/tasks.py)
            "tasks": node.task_manager.stats(),
            # per-shard recovery states (local-store opens on this
            # surface; staged peer/relocation recoveries on the
            # cluster's data nodes) — same shape as GET /_recovery
            "recoveries": _recovery_entries(node),
        }},
    }


def _engine_section(node):
    from elasticsearch_tpu.telemetry import engine as _engine
    cache = node.indices_service.device_cache
    out = {"compile": _engine.TRACKER.totals(),
           **cache.engine_stats()}
    fp = getattr(getattr(node, "_http", None), "fastpath", None)
    if fp is not None:
        # θ-cache of the native serving front, when one is running
        out["caches"]["theta"] = fp.engine_cache_stats()
    return out


def get_kernels(node, params, body):
    """GET /_kernels — the per-kernel compile table (telemetry/
    engine.py): shapes seen, compiles, cumulative compile ms, and the
    last-compile trigger. A kernel whose compile count grows with every
    call (ever-new shape keys) is a recompile storm; a shape-disciplined
    workload shows a flat table after warmup."""
    from elasticsearch_tpu.telemetry import engine as _engine
    out = {"kernels": _engine.TRACKER.to_dict(),
           "totals": _engine.TRACKER.totals(),
           "persistent_cache": _engine.TRACKER.persistent_stats()}
    fp = getattr(getattr(node, "_http", None), "fastpath", None)
    if fp is not None:
        # per-bucket dispatch counts + cohort histogram of the native
        # serving front — which warmed shapes actually earn their keep
        out["serving"] = fp.serving_stats()
    svc = getattr(node, "search_service", None)
    if svc is not None:
        # kNN cohort launches and the queries they carried (fill)
        out["knn"] = svc.knn_batcher.stats()
    mesh = getattr(svc, "mesh_executor", None)
    if mesh is not None:
        # multi-chip serving surface: dispatch counts per mesh axis,
        # typed fallback reasons, and per-DEVICE HBM residency of every
        # cached mesh corpus (parallel/mesh_executor.py)
        out["mesh"] = mesh.stats()
    return 200, out


def get_traces(node, params, body):
    """GET /_traces — newest-first summaries of the recent-trace ring;
    ``size``/``from`` page through it.

    ``exemplar_for=<metric>`` pivots the listing: instead of recency it
    returns the bounded per-bucket exemplars of that histogram (last
    trace.id + value per latency bucket, tail first), each resolved
    against the trace ring — a p99 spike in `_nodes/stats` navigates
    straight to a concrete traced (and, when profiled, profile-carrying)
    request."""
    metric = params.get("exemplar_for")
    if metric:
        tracer = node.telemetry.tracer
        exemplars = node.telemetry.metrics.exemplars_of(metric)
        for ex in exemplars:
            t = tracer.trace(ex["trace_id"])
            # resolvable=False: the trace has aged out of the bounded
            # ring; the exemplar's value/bucket still stand
            ex["resolvable"] = t is not None
            if t is not None:
                roots = [s for s in t["spans"]
                         if s["parent_id"] is None]
                ex["root"] = roots[0]["name"] if roots else None
                ex["spans"] = len(t["spans"])
        return 200, {"metric": metric, "exemplars": exemplars}
    limit = int(params.get("size", 32))
    offset = int(params.get("from", 0))
    return 200, {"traces":
                 node.telemetry.tracer.recent_traces(limit, offset)}


def get_trace(node, params, body, trace_id):
    """GET /_traces/{trace_id} — flat span list + nested span tree."""
    t = node.telemetry.tracer.trace(trace_id)
    if t is None:
        raise ResourceNotFoundException(f"unknown trace [{trace_id}]")
    return 200, t


def get_flight_recorder(node, params, body):
    """GET /_flight_recorder — this node's launch-path flight ring,
    newest first: every kernel launch (bucketed shape, cohort fill,
    queue-wait and dispatch nanos, regime tag) and every tracked
    device→host readback (site, bytes). Filters: ``kind=launch|
    readback``, ``kernel=``, ``site=``, ``trace_id=``, ``since_ns=``;
    ``size``/``from`` page. ``aggregates`` rides along — ring
    occupancy, fill histogram, readback-by-site, regime state."""
    fl = node.telemetry.flight
    events = fl.events(
        kind=params.get("kind"), kernel=params.get("kernel"),
        site=params.get("site"), trace_id=params.get("trace_id"),
        since_ns=(int(params["since_ns"])
                  if params.get("since_ns") else None),
        limit=int(params.get("size", 256)),
        offset=int(params.get("from", 0)))
    return 200, {"node": node.node_id, "events": events,
                 "aggregates": fl.aggregates()}


def get_flight_waterfall(node, params, body, trace_id):
    """GET /_flight_recorder/waterfall/{trace_id} — the request
    waterfall: the trace's span tree with this node's launch/readback
    events attached to the spans they ran under, plus per-span self
    time. On a cluster node the coordinator fans the same question out
    to every node and stitches one cross-node waterfall
    (``ClusterNode.flight_waterfall``); standalone it renders the
    local slice with the same ``build_waterfall`` merge."""
    from elasticsearch_tpu.telemetry import flightrecorder as _fl
    t = node.telemetry.tracer.trace(trace_id)
    events = node.telemetry.flight.events_for_trace(trace_id)
    if t is None and not events:
        raise ResourceNotFoundException(f"unknown trace [{trace_id}]")
    return 200, _fl.build_waterfall(trace_id, [{
        "node": node.node_id,
        "spans": (t or {}).get("spans", []),
        "events": events,
    }])


from contextlib import contextmanager


@contextmanager
def _rest_trace(node, name, **tags):
    """Root a trace at the REST boundary: the span is ambient for the
    handler body (service-level spans parent to it) and its trace id is
    echoed back in the `trace.id` response header. The same name is a
    host span in a profiler trace."""
    with host_span(name):
        tele = getattr(node, "telemetry", None)
        if tele is None:
            yield None
            return
        span = tele.tracer.start_span(name, tags=tags)
        try:
            with _telectx.activate_span(span):
                yield span
        finally:
            span.finish()


def indices_stats(node, params, body):
    out = {"indices": {name: idx.stats()
                       for name, idx in node.indices_service.indices.items()}}
    total_docs = sum(s["docs"]["count"] for s in out["indices"].values())
    out["_all"] = {"primaries": {"docs": {"count": total_docs}}}
    return 200, out


def cat_indices(node, params, body):
    lines = []
    for name in sorted(node.indices_service.indices):
        idx = node.indices_service.get(name)
        s = idx.stats()
        lines.append(f"green open {name} {idx.num_shards} 0 "
                     f"{s['docs']['count']} {s['docs']['deleted']}")
    return 200, {"_cat": "\n".join(lines)}


def cat_health(node, params, body):
    # same status source as _cluster/health (and the shards_availability
    # indicator): cat_health is a projection of cluster_health, not a
    # second implementation
    _, h = cluster_health(node, params, body)
    return 200, {"_cat": f"{int(time.time())} {node.cluster_name} "
                         f"{h['status']} {h['number_of_nodes']} "
                         f"{h['number_of_data_nodes']}"}


def cat_tenants(node, params, body):
    # projection of /_tenants/stats through the shared shaping helper —
    # one accounting implementation, two renders (json + columns)
    from elasticsearch_tpu.telemetry.tenants import render_cat_tenants
    _, merged = tenants_stats(node, params, body)
    return 200, {"_cat": render_cat_tenants(merged)}


def cat_workload(node, params, body):
    # projection of /_workload/stats through the shared shaping helper
    from elasticsearch_tpu.telemetry.workload import render_cat_workload
    _, merged = workload_stats(node, params, body)
    return 200, {"_cat": render_cat_workload(merged)}


def cat_count(node, params, body):
    docs = sum(idx.stats()["docs"]["count"]
               for idx in node.indices_service.indices.values())
    return 200, {"_cat": f"{int(time.time())} {docs}"}


def cat_shards(node, params, body):
    lines = []
    for name in sorted(node.indices_service.indices):
        idx = node.indices_service.get(name)
        for i, shard in enumerate(idx.shards):
            s = shard.stats()
            lines.append(f"{name} {i} p STARTED {s['docs']['count']} {node.name}")
    return 200, {"_cat": "\n".join(lines)}


# -- index admin -------------------------------------------------------------

def create_index(node, params, body, index):
    body = body or {}
    node.metadata_service.create_index_from_template(index, body)
    return 200, {"acknowledged": True, "shards_acknowledged": True,
                 "index": index}


def delete_index(node, params, body, index):
    for name in node.indices_service.resolve(index, allow_closed=True):
        node.indices_service.delete_index(name)
    return 200, {"acknowledged": True}


def get_index(node, params, body, index):
    out = {}
    for name in node.indices_service.resolve(index, allow_closed=True):
        idx = node.indices_service.get(name)
        out[name] = {"mappings": idx.mapper.to_mapping(),
                     "settings": {"index": idx.settings.by_prefix("index").as_nested_dict()}}
    return 200, out


def get_mapping(node, params, body, index):
    return 200, {name: {"mappings": node.indices_service.get(name).mapper.to_mapping()}
                 for name in node.indices_service.resolve(index,
                                                          allow_closed=True)}


def put_mapping(node, params, body, index):
    for name in node.indices_service.resolve(index):
        node.indices_service.get(name).update_mappings(body or {})
    return 200, {"acknowledged": True}


def get_settings(node, params, body, index):
    return 200, {name: {"settings": {"index": node.indices_service.get(name)
                                     .settings.by_prefix("index").as_nested_dict()}}
                 for name in node.indices_service.resolve(index,
                                                          allow_closed=True)}


def refresh_index(node, params, body, index):
    for name in node.indices_service.resolve(index):
        node.indices_service.get(name).refresh()
    return 200, {"_shards": {"successful": 1, "failed": 0}}


def flush_index(node, params, body, index):
    for name in node.indices_service.resolve(index):
        node.indices_service.get(name).flush()
    return 200, {"_shards": {"successful": 1, "failed": 0}}


def forcemerge_index(node, params, body, index):
    max_seg = int(params.get("max_num_segments", 1))
    for name in node.indices_service.resolve(index):
        node.indices_service.get(name).force_merge(max_seg)
    return 200, {"_shards": {"successful": 1, "failed": 0}}


def index_stats(node, params, body, index):
    return 200, {"indices": {name: node.indices_service.get(name).stats()
                             for name in node.indices_service.resolve(index)}}


def analyze(node, params, body, index):
    idx = node.indices_service.get(index)
    return _analyze(idx.mapper.analysis, body or {})


def analyze_no_index(node, params, body):
    from elasticsearch_tpu.analysis import AnalysisRegistry
    return _analyze(AnalysisRegistry(), body or {})


def _analyze(registry, body):
    text = body.get("text", "")
    texts = text if isinstance(text, list) else [text]
    if "tokenizer" in body or "filter" in body or "char_filter" in body:
        # ad-hoc chain (ref: TransportAnalyzeAction custom analysis):
        # components are names or inline definitions
        from elasticsearch_tpu.analysis.analyzers import (
            _CHAR_FILTERS, _TOKENIZERS, _TOKEN_FILTERS, CustomAnalyzer)

        def build(spec, reg, named, kind):
            if isinstance(spec, str):
                built = named.get(spec)
                if built is not None:
                    return built          # index-defined component
                name, conf = spec, {}
            else:
                conf = dict(spec)
                name = conf.get("type")
            factory = reg.get(name)
            if factory is None:
                raise IllegalArgumentException(
                    f"failed to find global {kind} under [{name}]")
            return factory(conf)

        named_toks = getattr(registry, "named_tokenizers", {})
        named_filters = getattr(registry, "named_filters", {})
        named_chars = getattr(registry, "named_char_filters", {})
        tok = build(body.get("tokenizer", "standard"),
                    _TOKENIZERS, named_toks, "tokenizer")
        filters = [build(f, _TOKEN_FILTERS, named_filters, "token filter")
                   for f in body.get("filter", [])]
        char_filters = [build(f, _CHAR_FILTERS, named_chars, "char filter")
                        for f in body.get("char_filter", [])]
        analyzer = CustomAnalyzer("_adhoc_", tok, filters, char_filters)
    else:
        analyzer = registry.get(body.get("analyzer", "standard"))

    def rows(toks):
        return [{"token": t.term, "start_offset": t.start_offset,
                 "end_offset": t.end_offset, "position": t.position,
                 "type": "<ALPHANUM>"} for t in toks]

    if body.get("explain") in (True, "true"):
        # per-stage attribution (ref: TransportAnalyzeAction detail
        # response / the DetailAnalyzeResponse shape): text after each
        # char filter, tokenizer output, then tokens after EVERY token
        # filter in chain order
        tokenizer = getattr(analyzer, "tokenizer", None)
        filters = list(getattr(analyzer, "token_filters", []) or [])
        char_filters = list(getattr(analyzer, "char_filters", []) or [])
        if tokenizer is None:
            return 200, {"detail": {
                "custom_analyzer": False,
                "analyzer": {
                    "name": body.get("analyzer", "standard"),
                    "tokens": rows([t for x in texts
                                    for t in analyzer.analyze(x)])}}}
        charfilter_out = []
        staged_texts = list(texts)
        for cf in char_filters:
            apply = getattr(cf, "apply", None) or cf.filter
            staged_texts = [apply(x) for x in staged_texts]
            charfilter_out.append({
                "name": getattr(cf, "name", type(cf).__name__),
                "filtered_text": list(staged_texts)})
        if getattr(tokenizer, "native_lowercase", False):
            # the fused native lowercase fast path would misattribute
            # case folding to the tokenizer stage — explain shows the
            # un-fused chain
            from elasticsearch_tpu.analysis.tokenizers import (
                StandardTokenizer as _Std)
            tokenizer = _Std(tokenizer.max_token_length)
        toks = [t for x in staged_texts for t in tokenizer.tokenize(x)]
        detail = {
            "custom_analyzer": True,
            "charfilters": charfilter_out,
            "tokenizer": {"name": getattr(tokenizer, "name", "?"),
                          "tokens": rows(toks)},
            "tokenfilters": [],
        }
        for f in filters:
            toks = f.filter(toks)
            detail["tokenfilters"].append({
                "name": getattr(f, "name", type(f).__name__),
                "tokens": rows(toks)})
        return 200, {"detail": detail}

    tokens = []
    for t in texts:
        tokens.extend(rows(analyzer.analyze(t)))
    return 200, {"tokens": tokens}


# -- documents ---------------------------------------------------------------

def _ensure_index(node, index):
    # aliases/data streams route writes to their write index (ref:
    # IndexAbstraction.getWriteIndex)
    index = node.metadata_service.write_target(index)
    if not node.indices_service.has(index):
        # auto-create on first write, applying matching templates (ref:
        # TransportBulkAction auto-create, TransportBulkAction.java:251-260)
        node.metadata_service.create_index_from_template(index)
    return node.indices_service.get(index)


def _write_response(index, result, created_word="created"):
    return {
        "_index": index,
        "_id": result.doc_id,
        "_version": result.version,
        "result": created_word,
        "_shards": {"total": 1, "successful": 1, "failed": 0},
        "_seq_no": result.seq_no,
        "_primary_term": result.primary_term,
    }


def _run_ingest(node, index, doc_id, params, source, routing=None):
    """The ingest detour before indexing (ref: TransportBulkAction.java:172
    → IngestService.executeBulkRequest). Returns (source, index, routing)
    — pipelines may reroute via ``_index``/``_routing`` metadata — or None
    if a drop processor discarded the doc."""
    pipeline_id = params.get("pipeline")
    if pipeline_id is None and node.indices_service.has(index):
        idx = node.indices_service.get(index)
        pipeline_id = idx.settings.get("index.default_pipeline")
    if pipeline_id in (None, "_none"):
        return source, index, routing
    doc = node.ingest_service.process(pipeline_id, index, doc_id, source,
                                      routing=routing)
    if doc is None:
        return None
    return (doc.source, doc.meta.get("_index") or index,
            doc.meta.get("_routing", routing))


def index_doc(node, params, body, index, id):
    ingested = _run_ingest(node, index, id, params, body or {},
                           routing=params.get("routing"))
    if ingested is None:  # dropped by pipeline
        return 200, {"_index": index, "_id": id, "result": "noop",
                     "_shards": {"total": 0, "successful": 0, "failed": 0}}
    body, index, routing = ingested
    params = dict(params)
    if routing is not None:
        params["routing"] = routing
    idx = _ensure_index(node, index)
    op_type = params.get("op_type", "index")
    kwargs = {}
    if "if_seq_no" in params:
        kwargs["if_seq_no"] = int(params["if_seq_no"])
        kwargs["if_primary_term"] = int(params.get("if_primary_term", 1))
    result = idx.index_doc(id, body or {}, routing=params.get("routing"),
                           op_type=op_type, **kwargs)
    if params.get("refresh") in ("true", "wait_for", ""):
        idx.refresh()
    status = 201 if result.created else 200
    return status, _write_response(
        index, result, "created" if result.created else "updated")


def index_doc_auto_id(node, params, body, index):
    return index_doc(node, params, body, index, uuid.uuid4().hex[:20])


def create_doc(node, params, body, index, id):
    params = dict(params)
    params["op_type"] = "create"
    return index_doc(node, params, body, index, id)


def get_doc(node, params, body, index, id):
    index = node.metadata_service.write_target(index)
    idx = node.indices_service.get(index)
    result = idx.get_doc(id, routing=params.get("routing"))
    if not result.found:
        return 404, {"_index": index, "_id": id, "found": False}
    out = {"_index": index, "_id": id, "_version": result.version,
           "_seq_no": result.seq_no, "_primary_term": result.primary_term,
           "found": True, "_source": result.source}
    return 200, out


def get_source(node, params, body, index, id):
    index = node.metadata_service.write_target(index)
    idx = node.indices_service.get(index)
    result = idx.get_doc(id, routing=params.get("routing"))
    if not result.found:
        raise DocumentMissingException(index, id)
    return 200, result.source


def delete_doc(node, params, body, index, id):
    index = node.metadata_service.write_target(index)
    idx = node.indices_service.get(index)
    result = idx.delete_doc(id, routing=params.get("routing"))
    if params.get("refresh") in ("true", ""):
        idx.refresh()
    if not result.found:
        return 404, _write_response(index, result, "not_found")
    return 200, _write_response(index, result, "deleted")


def update_doc(node, params, body, index, id):
    """ref: UpdateHelper get-merge-reindex (action/update/)."""
    index = node.metadata_service.write_target(index)
    idx = node.indices_service.get(index)
    body = body or {}
    current = idx.get_doc(id, routing=params.get("routing"))
    if not current.found:
        if "upsert" in body:
            result = idx.index_doc(id, body["upsert"],
                                   routing=params.get("routing"))
            return 201, _write_response(index, result, "created")
        raise DocumentMissingException(index, id)
    if "doc" in body:
        merged = _deep_merge(current.source, body["doc"])
        if merged == current.source and body.get("detect_noop", True):
            result_shell = type("R", (), {
                "doc_id": id, "version": current.version,
                "seq_no": current.seq_no, "primary_term": current.primary_term})
            return 200, _write_response(index, result_shell, "noop")
        result = idx.index_doc(id, merged, routing=params.get("routing"))
        if params.get("refresh") in ("true", ""):
            idx.refresh()
        return 200, _write_response(index, result, "updated")
    if "script" in body:
        # scripted update (ref: UpdateHelper.executeScriptedUpsert /
        # prepareUpdateScriptRequest — ctx._source mutation, ctx.op)
        from elasticsearch_tpu.reindex.worker import (_Ctx,
                                                      compile_update_script)
        spec = body["script"]
        script = compile_update_script(spec)
        import copy
        src = copy.deepcopy(current.source)
        ctx = _Ctx(src, index, id, current.version)
        script.run(ctx)
        if ctx.op == "none" or ctx.op == "noop":
            result_shell = type("R", (), {
                "doc_id": id, "version": current.version,
                "seq_no": current.seq_no,
                "primary_term": current.primary_term})
            return 200, _write_response(index, result_shell, "noop")
        if ctx.op == "delete":
            result = idx.delete_doc(id, routing=params.get("routing"))
            if params.get("refresh") in ("true", ""):
                idx.refresh()
            return 200, _write_response(index, result, "deleted")
        result = idx.index_doc(id, src, routing=params.get("routing"))
        if params.get("refresh") in ("true", ""):
            idx.refresh()
        return 200, _write_response(index, result, "updated")
    raise IllegalArgumentException(
        "update requires [doc], [script], or [upsert]")


def _deep_merge(base, update):
    out = dict(base)
    for k, v in update.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def mget_index(node, params, body, index):
    docs = []
    for spec in (body or {}).get("docs", []):
        did = spec.get("_id")
        code, doc = get_doc(node, params, None, spec.get("_index", index), did)
        docs.append(doc)
    ids = (body or {}).get("ids")
    if ids:
        for did in ids:
            code, doc = get_doc(node, params, None, index, did)
            docs.append(doc)
    return 200, {"docs": docs}


def mget_all(node, params, body):
    docs = []
    for spec in (body or {}).get("docs", []):
        code, doc = get_doc(node, params, None, spec["_index"], spec["_id"])
        docs.append(doc)
    return 200, {"docs": docs}


# -- bulk --------------------------------------------------------------------

def bulk(node, params, body, index=None):
    """NDJSON bulk (ref: action/bulk/TransportBulkAction.java:100,172 —
    grouped per shard; here executed item-by-item against local shards).

    Coordinating admission happens FIRST: the raw payload bytes charge
    the node's indexing pressure and past the limit the whole bulk is
    rejected with a retryable 429 (EsRejectedExecutionException) before
    any parsing or shard work — overload sheds at the door (ref:
    IndexingPressure.markCoordinatingOperationStarted in
    TransportBulkAction)."""
    from elasticsearch_tpu.index.pressure import operation_size_bytes
    from elasticsearch_tpu.telemetry import context as _telectx
    ip = getattr(node, "indexing_pressure", None)
    with _telectx.activate_workload_class(
            _telectx.current_workload_class() or "bulk"):
        release = None
        if ip is not None:
            nbytes = (len(body) if isinstance(body, (bytes, str))
                      else operation_size_bytes(body))
            release = ip.mark_coordinating_operation_started(
                nbytes, "_bulk")
        try:
            return _bulk_inner(node, params, body, index)
        finally:
            # release-on-completion: in-flight bytes return to zero as
            # soon as the response (or rejection) is determined
            if release is not None:
                release()


def _bulk_inner(node, params, body, index=None):
    if isinstance(body, (bytes, str)):
        text = body.decode() if isinstance(body, bytes) else body
        try:
            if text.lstrip().startswith("["):
                # a JSON-array body in any formatting (compact or
                # pretty-printed) parses as one document
                lines = json.loads(text)
            else:
                lines = [json.loads(l) for l in text.splitlines()
                         if l.strip()]
        except ValueError as e:
            raise ParsingException(
                f"Failed to parse bulk body: {e}")
    elif isinstance(body, list):
        lines = body
    else:
        raise IllegalArgumentException("bulk body must be NDJSON")
    # a parsed-upstream one-line array wraps the request in one element
    if len(lines) == 1 and isinstance(lines[0], list):
        lines = lines[0]
    items = []
    errors = False
    i = 0
    start = time.monotonic()
    touched = set()
    while i < len(lines):
        action_line = lines[i]
        i += 1
        (action, meta), = action_line.items()
        target = meta.get("_index", index)
        doc_id = meta.get("_id") or uuid.uuid4().hex[:20]
        # consume the source line FIRST so a failing item can never
        # desynchronize the action/source alternation for later items
        source = None
        if action in ("index", "create", "update"):
            if i >= len(lines):
                raise IllegalArgumentException(
                    "Malformed bulk request: missing source for last action")
            source = lines[i]
            i += 1
        try:
            if target is None:
                raise IllegalArgumentException("bulk item missing _index")
            routing = meta.get("routing")
            if action in ("index", "create"):
                # per-item pipeline overrides the URL-level param (ref:
                # BulkRequest item pipelines)
                item_params = params
                if "pipeline" in meta:
                    item_params = dict(params)
                    item_params["pipeline"] = meta["pipeline"]
                ingested = _run_ingest(node, target, doc_id, item_params,
                                       source, routing=routing)
                if ingested is None:  # dropped by pipeline
                    items.append({action: {
                        "_index": target, "_id": doc_id,
                        "result": "noop", "status": 200}})
                    continue
                source, target, routing = ingested
            idx = _ensure_index(node, target)
            touched.add(target)
            if action in ("index", "create"):
                result = idx.index_doc(
                    doc_id, source, routing=routing,
                    op_type="create" if action == "create" else "index")
                items.append({action: {
                    "_index": target, "_id": result.doc_id,
                    "_version": result.version,
                    "result": "created" if result.created else "updated",
                    "_seq_no": result.seq_no, "status": 201 if result.created else 200}})
            elif action == "delete":
                result = idx.delete_doc(doc_id, routing=meta.get("routing"))
                items.append({action: {
                    "_index": target, "_id": doc_id,
                    "result": "deleted" if result.found else "not_found",
                    "status": 200 if result.found else 404}})
            elif action == "update":
                code, resp = update_doc(node, dict(params), source, target, doc_id)
                items.append({action: {**resp, "status": code}})
            else:
                raise IllegalArgumentException(f"Malformed action [{action}]")
        except ElasticsearchTpuException as e:
            errors = True
            items.append({action: {"_index": target, "_id": doc_id,
                                   "status": e.status,
                                   "error": e.to_xcontent()}})
    if params.get("refresh") in ("true", "wait_for", ""):
        for name in touched:
            node.indices_service.get(name).refresh()
    return 200, {"took": int((time.monotonic() - start) * 1000),
                 "errors": errors, "items": items}


def bulk_index(node, params, body, index):
    return bulk(node, params, body, index=index)


# -- search ------------------------------------------------------------------

def _current_user(node):
    return getattr(node.request_context, "user", None)


def _apply_dls(node, index, body):
    """AND the authenticated user's DLS query into the search (ref:
    SecurityIndexReaderWrapper — the role query becomes a filter bitset
    intersected with the scorer; here it joins the query plan and is one
    more mask intersect on device)."""
    user = _current_user(node)
    if user is None or not node.security_service.enabled:
        return body
    names = (node.indices_service.resolve(index)
             if index not in (None, "*", "_all") else
             list(node.indices_service.indices))
    queries = [node.security_service.dls_query(user, n) for n in names]
    queries = [q for q in queries if q is not None]
    if not queries:
        return body
    dls = (queries[0] if len(queries) == 1 else
           {"bool": {"should": queries, "minimum_should_match": 1}})
    body = dict(body or {})
    query = body.get("query")
    body["query"] = {"bool": {"must": [query] if query else [],
                              "filter": [dls]}}
    return body


def _apply_fls(node, index, result):
    """Filter hit sources by the user's field security grants."""
    user = _current_user(node)
    if user is None or not node.security_service.enabled:
        return result
    sec = node.security_service
    hits = result.get("hits", {}).get("hits", []) if isinstance(result, dict) \
        else []
    for hit in hits:
        fls = sec.fls_filter(user, hit.get("_index", index))
        if fls is not None and isinstance(hit.get("_source"), dict):
            hit["_source"] = sec.filter_source(hit["_source"], fls)
    return result


def _apply_alias_filter(node, index, body):
    """Filtered-alias search (ref: AliasFilter applied per shard request):
    wrap the query with the alias filter when the target is one alias."""
    filt = node.metadata_service.alias_filter(index)
    if filt is None:
        return body
    body = dict(body or {})
    query = body.get("query")
    body["query"] = {"bool": {"must": [query] if query else [],
                              "filter": [filt]}}
    return body


def search_index(node, params, body, index):
    body = _merge_search_params(body, params)
    if node.remote_cluster_service.has_remotes and ":" in index:
        return 200, _ccs_search(node, index, body)
    body = _apply_alias_filter(node, index, body)
    body = _apply_dls(node, index, body)
    with _rest_trace(node, "rest.search", index=index) as trace_span, \
            node.task_manager.task_scope(
                "transport", "indices:data/read/search",
                description=f"indices[{index}]", cancellable=True) as task:
        # through the action seam (ref: RestSearchAction →
        # client.execute(SearchAction.INSTANCE, ...))
        from elasticsearch_tpu.action import SEARCH

        def run():
            return node.client.execute(
                SEARCH, index, body, scroll=params.get("scroll"),
                task=task, search_type=params.get("search_type"))

        if _targets_only_frozen(node, index):
            # frozen-tier searches serialize on the search_throttled
            # pool (ref: ThreadPool.Names.SEARCH_THROTTLED — one
            # thread) so rehydrating cold HBM state can't starve hot
            # searches; bind() carries the ambient trace context across
            # the executor boundary
            from elasticsearch_tpu.telemetry import context as _telectx
            r = node.threadpool.executor("search_throttled") \
                .submit(_telectx.bind(run)).result(timeout=300)
        else:
            r = run()
    r = _apply_fls(node, index, r)
    if trace_span is not None:
        # the reference echoes the APM trace id on search responses
        r.setdefault("_headers", {})["trace.id"] = trace_span.trace_id
    return 200, r


def _targets_only_frozen(node, index_expression: str) -> bool:
    try:
        names = node.indices_service.resolve(index_expression)
    except Exception:   # noqa: BLE001 — resolution errors surface later
        return False
    if not names:
        return False
    return all(node.indices_service.get(n).is_frozen for n in names)


def search_all(node, params, body):
    body = _merge_search_params(body, params)
    body = _apply_dls(node, "_all", body)
    with _rest_trace(node, "rest.search", index="_all") as trace_span, \
            node.task_manager.task_scope(
                "transport", "indices:data/read/search",
                description="indices[_all]", cancellable=True) as task:
        r = node.search_service.search(
            "_all", body, scroll=params.get("scroll"), task=task,
            search_type=params.get("search_type"))
    r = _apply_fls(node, "_all", r)
    if trace_span is not None:
        r.setdefault("_headers", {})["trace.id"] = trace_span.trace_id
    return 200, r


def _merge_search_params(body, params):
    body = dict(body or {})
    if "q" in params and "query" not in body:
        # query_string lite: field:value or bare text on _all fields
        q = params["q"]
        if ":" in q:
            field, _, value = q.partition(":")
            body["query"] = {"match": {field: value}}
        else:
            body["query"] = {"multi_match": {"query": q, "fields": ["*"]}}
    for key in ("from", "size"):
        if key in params:
            body[key] = int(params[key])
    for key in ("request_cache", "allow_partial_search_results"):
        if key in params:
            body[key] = _bool_param(params, key)
    if "timeout" in params:
        body["timeout"] = params["timeout"]
    return body


def _bool_param(params, key: str) -> bool:
    v = params[key]
    if v not in ("true", "false"):
        raise IllegalArgumentException(
            f"Failed to parse value [{v}] as only [true] or [false] "
            "are allowed.")
    return v == "true"


def count_index(node, params, body, index):
    body = _apply_alias_filter(node, index, body or {})
    body = _apply_dls(node, index, body)
    return 200, node.search_service.count(index, body)


def explain_doc(node, params, body, index, id):
    body = body or {}
    if "q" in params and "query" not in body:
        body = _merge_search_params(body, params)
    body = _apply_alias_filter(node, index, body)
    return 200, node.search_service.explain(index, id, body)


def scroll(node, params, body):
    body = body or {}
    scroll_id = body.get("scroll_id") or params.get("scroll_id")
    keep = body.get("scroll") or params.get("scroll")
    return 200, node.search_service.scroll(scroll_id, keep)


def clear_scroll(node, params, body):
    ids = (body or {}).get("scroll_id", ["_all"])
    if isinstance(ids, str):
        ids = [ids]
    freed = node.search_service.clear_scroll(ids)
    return 200, {"succeeded": True, "num_freed": freed}


def msearch(node, params, body, index=None):
    lines = _ndjson_lines(body)
    searches = []
    i = 0
    while i + 1 < len(lines) or (i < len(lines) and index):
        header = lines[i]
        i += 1
        target = header.get("index", index) or "_all"
        search_body = lines[i] if i < len(lines) else {}
        i += 1
        searches.append((target, search_body))

    # one cancellable parent for the msearch; each sub-search runs as a
    # cancellable child task under it, so cancelling the parent stops
    # queued sub-searches too (the ban table kills late children)
    from elasticsearch_tpu.transport.tasks import TaskId as _TaskId
    parent = node.task_manager.register(
        "transport", "indices:data/read/msearch",
        description=f"requests[{len(searches)}]", cancellable=True)

    def one(target, search_body):
        sub = node.task_manager.register(
            "transport", "indices:data/read/search",
            description=f"indices[{target}]",
            parent_task_id=_TaskId(node.node_id, parent.id),
            cancellable=True)
        try:
            search_body = _apply_alias_filter(node, target, search_body)
            return node.search_service.search(target, search_body,
                                              task=sub)
        except ElasticsearchTpuException as e:
            return {"error": e.to_xcontent(), "status": e.status}
        finally:
            node.task_manager.unregister(sub)

    # sub-searches fan out on the SEARCH pool (ref:
    # TransportMultiSearchAction executing per-request on the search
    # executor) — concurrent sub-searches also coalesce into shared
    # batched launches downstream
    try:
        if len(searches) > 1:
            from elasticsearch_tpu.common.threadpool import (
                EsRejectedExecutionException)
            futures = []
            for t, b in searches:
                try:
                    futures.append(
                        node.threadpool.executor("search").submit(one, t,
                                                                  b))
                except EsRejectedExecutionException as e:
                    # a full search queue rejects THIS sub-search with
                    # 429, never the whole msearch (ref: per-item
                    # rejection in TransportMultiSearchAction)
                    futures.append({
                        "error": {
                            "type": "es_rejected_execution_exception",
                            "reason": str(e)}, "status": 429})
            responses = [f.result() if hasattr(f, "result") else f
                         for f in futures]
        else:
            responses = [one(t, b) for t, b in searches]
    finally:
        node.task_manager.unregister(parent)
    return 200, {"responses": responses}


def msearch_index(node, params, body, index):
    return msearch(node, params, body, index=index)


# -- search utility APIs -----------------------------------------------------

def field_caps(node, params, body, index="_all"):
    """ref: action/fieldcaps/TransportFieldCapabilitiesAction — merge
    per-index field capabilities; `indices` listed per cap entry only
    where types conflict."""
    import fnmatch
    patterns = params.get("fields", "*").split(",")
    if body and "fields" in body:
        patterns = (body["fields"] if isinstance(body["fields"], list)
                    else body["fields"].split(","))
    names = node.indices_service.resolve(index)
    # field -> type -> {indices: [...], searchable, aggregatable}
    out: Dict[str, Dict[str, Dict[str, Any]]] = {}
    for name in names:
        idx = node.indices_service.get(name)
        for fname in idx.mapper.field_names():
            if not any(fnmatch.fnmatch(fname, p.strip()) for p in patterns):
                continue
            ft = idx.mapper.field_type(fname)
            t = ft.type_name
            caps = out.setdefault(fname, {}).setdefault(t, {
                "type": t,
                "metadata_field": fname.startswith("_"),
                "searchable": getattr(ft, "searchable", True),
                "aggregatable": t not in ("text",),
                "_indices": [],
            })
            caps["_indices"].append(name)
    result: Dict[str, Any] = {}
    for fname, types in out.items():
        entry = {}
        for t, caps in types.items():
            c = dict(caps)
            idx_list = c.pop("_indices")
            if len(types) > 1:  # only list indices when types conflict
                c["indices"] = sorted(idx_list)
            entry[t] = c
        result[fname] = entry
    return 200, {"indices": sorted(names), "fields": result}


def validate_query(node, params, body, index):
    """ref: action/admin/indices/validate/query — parse/rewrite the query,
    report validity with optional explanation."""
    from elasticsearch_tpu.search.queries import parse_query
    body = body or {}
    q = body.get("query", {"match_all": {}})
    try:
        parsed = parse_query(q)
        explanation = repr(parsed) if params.get("explain") in ("true", "") \
            else None
        exp = [{"index": n, "valid": True,
                **({"explanation": explanation} if explanation else {})}
               for n in node.indices_service.resolve(index)]
        return 200, {"valid": True,
                     "_shards": {"total": 1, "successful": 1, "failed": 0},
                     "explanations": exp if explanation else []}
    except ElasticsearchTpuException as e:
        return 200, {"valid": False, "error": str(e)}


def terms_enum(node, params, body, index):
    """ref: x-pack terms-enum — prefix-complete terms from the index
    dictionaries (postings terms + keyword doc-value terms)."""
    body = body or {}
    field = body.get("field") or params.get("field")
    if not field:
        raise IllegalArgumentException("terms_enum requires [field]")
    prefix = body.get("string", params.get("string", ""))
    size = int(body.get("size", params.get("size", 10)))
    case_insensitive = bool(body.get("case_insensitive"))
    cmp_prefix = prefix.lower() if case_insensitive else prefix
    found = set()
    for name in node.indices_service.resolve(index):
        idx = node.indices_service.get(name)
        for searcher in idx.shard_searchers():
            for seg in searcher.segments:
                pf = seg.postings.get(field)
                if pf is not None:
                    for t in pf.terms:
                        probe = t.lower() if case_insensitive else t
                        if probe.startswith(cmp_prefix):
                            found.add(t)
                kv = seg.keywords.get(field)
                if kv is not None:
                    for t in kv.terms:
                        probe = t.lower() if case_insensitive else t
                        if probe.startswith(cmp_prefix):
                            found.add(t)
    return 200, {"terms": sorted(found)[:size], "complete": True,
                 "_shards": {"total": 1, "successful": 1, "failed": 0}}


def resolve_index(node, params, body, expression):
    """ref: action/admin/indices/resolve/ResolveIndexAction."""
    import fnmatch
    meta = node.metadata_service
    index_names, alias_names, stream_names = set(), set(), set()
    for part in expression.split(","):
        if part == "_all":
            part = "*"
        index_names.update(n for n in node.indices_service.indices
                           if fnmatch.fnmatch(n, part))
        alias_names.update(a for a in meta.aliases
                           if fnmatch.fnmatch(a, part))
        stream_names.update(ds for ds in meta.data_streams
                            if fnmatch.fnmatch(ds, part))
    return 200, {
        "indices": [{"name": n, "attributes": ["open"]}
                    for n in sorted(index_names)],
        "aliases": [{"name": a, "indices": sorted(meta.aliases[a])}
                    for a in sorted(alias_names)],
        "data_streams": [{"name": ds,
                          "backing_indices":
                              meta.data_streams[ds].get("indices", []),
                          "timestamp_field": "@timestamp"}
                         for ds in sorted(stream_names)],
    }


def open_pit(node, params, body, index):
    keep_alive = params.get("keep_alive", "1m")
    pit_id = node.search_service.open_pit(index, keep_alive)
    return 200, {"id": pit_id}


def close_pit(node, params, body):
    pit_id = (body or {}).get("id")
    if not pit_id:
        raise IllegalArgumentException("close PIT requires [id]")
    ok = node.search_service.close_pit(pit_id)
    return (200 if ok else 404), {"succeeded": ok,
                                  "num_freed": 1 if ok else 0}


# -- stored scripts + search templates ---------------------------------------

def put_stored_script(node, params, body, id):
    node.stored_scripts.put(id, (body or {}).get("script", {}))
    return 200, {"acknowledged": True}


def get_stored_script(node, params, body, id):
    script = node.stored_scripts.get(id)
    if script is None:
        return 404, {"_id": id, "found": False}
    return 200, {"_id": id, "found": True, "script": script}


def delete_stored_script(node, params, body, id):
    if not node.stored_scripts.delete(id):
        raise ResourceNotFoundException(f"stored script [{id}] does not exist")
    return 200, {"acknowledged": True}


def _resolve_template(node, body):
    from elasticsearch_tpu.search.template import render_template
    body = body or {}
    source = body.get("source")
    if source is None and body.get("id"):
        stored = node.stored_scripts.get(body["id"])
        if stored is None:
            raise ResourceNotFoundException(
                f"stored script [{body['id']}] does not exist")
        source = stored["source"]
    if source is None:
        raise IllegalArgumentException(
            "search template requires [source] or [id]")
    return render_template(source, body.get("params"))


def render_search_template(node, params, body, id=None):
    if id is not None:
        body = dict(body or {})
        body["id"] = id
    return 200, {"template_output": _resolve_template(node, body)}


def search_template(node, params, body, index):
    rendered = _resolve_template(node, body)
    rendered = _apply_alias_filter(node, index, rendered)
    return 200, node.search_service.search(index, rendered)


def search_template_all(node, params, body):
    return search_template(node, params, body, "_all")


def msearch_template(node, params, body, index=None):
    lines = _ndjson_lines(body)
    responses = []
    i = 0
    while i + 1 < len(lines) or (i < len(lines) and index):
        header = lines[i]
        i += 1
        target = header.get("index", index) or "_all"
        spec = lines[i] if i < len(lines) else {}
        i += 1
        try:
            rendered = _resolve_template(node, spec)
            rendered = _apply_alias_filter(node, target, rendered)
            responses.append(node.search_service.search(target, rendered))
        except ElasticsearchTpuException as e:
            responses.append({"error": e.to_xcontent(), "status": e.status})
    if i < len(lines):
        raise IllegalArgumentException(
            "msearch template body has a trailing header with no body line")
    return 200, {"responses": responses}


def _ndjson_lines(body):
    if isinstance(body, (bytes, str)):
        return [json.loads(l) for l in
                (body.decode() if isinstance(body, bytes) else body).splitlines()
                if l.strip()]
    return body or []


# -- reindex family ----------------------------------------------------------

def _bulk_by_scroll(node, params, action_name, run):
    """Run a reindex-family worker, sync or as a background task
    (``wait_for_completion=false`` → returns {"task": id}, result stored
    for GET /_tasks/{id}; ref: reindex tasks store results in .tasks).

    The worker drains its source through the resumable cursor path
    (search/service.py resumable_scroll_batches): a scroll context lost
    mid-drain re-opens at the last continuation point, so a copy
    failure retries from where the drain was — the operation never
    restarts from scratch and never double-applies a batch."""
    import threading
    if params.get("wait_for_completion") == "false":
        task = node.task_manager.register("transport", action_name,
                                          cancellable=True)

        def runner():
            try:
                resp = run(task)
                _store_task_result(node, task.id, resp.to_dict())
            except ElasticsearchTpuException as e:
                _store_task_result(node, task.id, {"error": e.to_xcontent()})
            except Exception as e:  # never lose a background failure
                _store_task_result(node, task.id, {"error": {
                    "type": type(e).__name__, "reason": str(e)}})
            finally:
                node.task_manager.unregister(task)

        threading.Thread(target=runner, daemon=True).start()
        return 200, {"task": f"{node.node_id}:{task.id}"}
    with node.task_manager.task_scope("transport", action_name,
                                      cancellable=True) as task:
        resp = run(task)
    return 200, resp.to_dict()


def _store_task_result(node, task_id, result):
    node.task_results[task_id] = result
    while len(node.task_results) > 256:
        node.task_results.popitem(last=False)
    # persist into the .tasks system index (ref: the `tasks` module —
    # TaskResultsService writes completed task results to .tasks so they
    # survive restarts and are queryable like any document)
    try:
        if not node.indices_service.has(".tasks"):
            node.indices_service.create_index(".tasks", None, {
                "properties": {"completed": {"type": "boolean"},
                               "task_id": {"type": "keyword"},
                               "task_num": {"type": "long"}}})
        idx = node.indices_service.get(".tasks")
        idx.index_doc(
            f"{node.node_id}:{task_id}",
            {"completed": True, "task_id": f"{node.node_id}:{task_id}",
             "task_num": int(task_id), "response": result})
        idx.flush()   # durable: results must survive restarts
    except Exception:   # noqa: BLE001 — result storage must never fail
        pass            # the originating operation (ref: best-effort
        # TaskResultsService.storeResult error handler)


def reindex_handler(node, params, body):
    from elasticsearch_tpu.reindex import reindex
    return _bulk_by_scroll(node, params, "indices:data/write/reindex",
                           lambda task: reindex(node, body, params, task=task))


def update_by_query_handler(node, params, body, index):
    from elasticsearch_tpu.reindex import update_by_query
    return _bulk_by_scroll(
        node, params, "indices:data/write/update/byquery",
        lambda task: update_by_query(node, index, body, params, task=task))


def delete_by_query_handler(node, params, body, index):
    from elasticsearch_tpu.reindex import delete_by_query
    return _bulk_by_scroll(
        node, params, "indices:data/write/delete/byquery",
        lambda task: delete_by_query(node, index, body, params, task=task))


def rethrottle_handler(node, params, body, task_id):
    task = _local_task(node, task_id)
    throttle = getattr(task, "reindex_throttle", None)
    if throttle is not None and "requests_per_second" in params:
        raw = params["requests_per_second"]
        throttle.rps = -1.0 if raw in ("-1", "unlimited") else float(raw)
    return 200, {"nodes": {node.node_id: {
        "tasks": {task_id: task.to_dict(node.node_id)}}}}


# -- tasks / async search ----------------------------------------------------

def _node_task_infos(node, actions=None, parent_task_id=None,
                     detailed=True):
    """This node's `_tasks` slice in the fan-out shape — the same
    per-node map `ClusterNode.list_tasks` merges, so the single-node
    REST surface and the cluster fan-out render identically
    (transport/tasks.py shaping)."""
    from elasticsearch_tpu.transport.tasks import node_task_slice
    return {node.node_id: node_task_slice(
        node.task_manager, node.node_id, name=node.name,
        actions=actions, parent_task_id=parent_task_id,
        detailed=detailed)}


def list_tasks(node, params, body):
    """GET /_tasks with `detailed`, `actions`, `parent_task_id` and
    `group_by=nodes|parents|none` (ref: RestListTasksAction)."""
    from elasticsearch_tpu.transport.tasks import (
        build_tasks_response,
        parse_bool_param,
    )
    infos = _node_task_infos(
        node, actions=params.get("actions"),
        parent_task_id=params.get("parent_task_id"),
        detailed=parse_bool_param(params.get("detailed"), False))
    return 200, build_tasks_response(
        infos, group_by=params.get("group_by", "nodes"))


def _local_task(node, task_id):
    tid = TaskId.parse(task_id)
    if tid.node_id not in ("", node.node_id):
        # a task id minted by another node must not alias a local task
        raise ResourceNotFoundException(f"task [{task_id}] is not found")
    task = node.task_manager.get_task(tid.id)
    if task is None:
        raise ResourceNotFoundException(f"task [{task_id}] isn't running "
                                        "and hasn't stored its results")
    return task


def get_task(node, params, body, task_id):
    tid = TaskId.parse(task_id)
    stored = node.task_results.get(tid.id)
    if stored is not None and tid.node_id in ("", node.node_id):
        return 200, {"completed": True, "response": stored,
                     "task": {"node": node.node_id, "id": tid.id}}
    if stored is None and tid.node_id in ("", node.node_id) \
            and node.indices_service.has(".tasks"):
        # restart survival: completed results live in the .tasks system
        # index (ref: the `tasks` module / TaskResultsService). Node ids
        # change across restarts, so bare task numbers resolve by query.
        g = node.indices_service.get(".tasks").get_doc(
            f"{node.node_id}:{tid.id}")
        src = g.source if g.found else None
        if src is None and tid.node_id == "":
            r = node.search_service.search(".tasks", {
                "query": {"term": {"task_num": tid.id}}, "size": 1})
            hits = r["hits"]["hits"]
            src = hits[0]["_source"] if hits else None
        if src is not None:
            return 200, {"completed": True,
                         "response": src.get("response"),
                         "task": {"node": node.node_id, "id": tid.id}}
    task = _local_task(node, task_id)
    if params.get("wait_for_completion") == "true":
        deadline = time.monotonic() + float(params.get("timeout_s", 30))
        while time.monotonic() < deadline:
            stored = node.task_results.get(tid.id)
            if stored is not None:
                return 200, {"completed": True, "response": stored,
                             "task": {"node": node.node_id, "id": tid.id}}
            if node.task_manager.get_task(tid.id) is None:
                # finished without storing a result (e.g. a plain search
                # task) — completed, nothing to return
                return 200, {"completed": True,
                             "task": {"node": node.node_id, "id": tid.id}}
            time.sleep(0.02)
    return 200, {"completed": False, "task": task.to_dict(node.node_id)}


def cancel_task(node, params, body, task_id):
    task = _local_task(node, task_id)
    if not isinstance(task, CancellableTask):
        raise IllegalArgumentException(
            f"task [{task_id}] is not cancellable")
    node.task_manager.cancel(task, params.get("reason", "by user request"))
    return 200, {"nodes": {node.node_id: {
        "tasks": {task_id: task.to_dict(node.node_id)}}}}


def cancel_tasks(node, params, body):
    cancelled = {}
    for t in node.task_manager.list_tasks(actions=params.get("actions")):
        if isinstance(t, CancellableTask):
            node.task_manager.cancel(t, "by user request")
            cancelled[f"{node.node_id}:{t.id}"] = t.to_dict(node.node_id)
    return 200, {"nodes": {node.node_id: {"tasks": cancelled}}}


def submit_async_search(node, params, body, index=None):
    body = _merge_search_params(body, params)
    target = index or "_all"
    body = _apply_alias_filter(node, target, body)
    r = node.async_search_service.submit(target, body, params)
    return r.pop("_http_status", 200), r


def get_async_search(node, params, body, id):
    r = node.async_search_service.get(id, params)
    return r.pop("_http_status", 200), r


def delete_async_search(node, params, body, id):
    node.async_search_service.delete(id)
    return 200, {"acknowledged": True}


# -- aliases / templates / data streams / rollover ---------------------------

def update_aliases(node, params, body):
    node.metadata_service.update_aliases((body or {}).get("actions", []))
    return 200, {"acknowledged": True}


def put_alias(node, params, body, index, name):
    spec = {"index": index, "alias": name}
    spec.update(body or {})
    node.metadata_service.update_aliases([{"add": spec}])
    return 200, {"acknowledged": True}


def delete_alias(node, params, body, index, name):
    node.metadata_service.update_aliases(
        [{"remove": {"index": index, "alias": name}}])
    return 200, {"acknowledged": True}


def get_alias(node, params, body, index=None, name=None):
    out = node.metadata_service.get_aliases(index, name)
    if name and not out:
        return 404, {"error": f"alias [{name}] missing", "status": 404}
    return 200, out


def cat_aliases(node, params, body):
    lines = []
    for a, members in sorted(node.metadata_service.aliases.items()):
        for idx in sorted(members):
            lines.append(f"{a} {idx} - - - -")
    return 200, {"_cat": "\n".join(lines)}


def cluster_pending_tasks(node, params, body):
    """ref: RestPendingClusterTasksAction — tasks queued on the master
    service (real queue entries when a coordinator is attached; the
    single-node container applies state updates synchronously, so its
    queue reads empty)."""
    return 200, {"tasks": _pending_cluster_tasks(node)}


def add_index_block(node, params, body, index, block):
    """ref: RestAddIndexBlockAction — PUT /{index}/_block/{block}
    sets the matching index.blocks.* setting."""
    if block not in ("write", "read", "read_only", "metadata"):
        raise IllegalArgumentException(f"invalid block [{block}]")
    names = node.indices_service.resolve(index)
    for name in names:
        idx = node.indices_service.get(name)
        # update_settings persists the block across restarts (the
        # pattern every other block writer uses)
        idx.update_settings({f"index.blocks.{block}": True})
    return 200, {"acknowledged": True, "shards_acknowledged": True,
                 "indices": [{"name": n, "blocked": True}
                             for n in names]}


def put_index_template(node, params, body, name):
    node.metadata_service.put_index_template(name, body or {})
    return 200, {"acknowledged": True}


def get_index_template(node, params, body, name=None):
    tmpls = node.metadata_service.index_templates
    if name and name not in tmpls:
        raise ResourceNotFoundException(
            f"index template matching [{name}] not found")
    wanted = [name] if name else sorted(tmpls)
    return 200, {"index_templates": [
        {"name": n, "index_template": tmpls[n]} for n in wanted]}


def delete_index_template(node, params, body, name):
    node.metadata_service.delete_index_template(name)
    return 200, {"acknowledged": True}


def put_component_template(node, params, body, name):
    node.metadata_service.put_component_template(name, body or {})
    return 200, {"acknowledged": True}


def get_component_template(node, params, body, name=None):
    tmpls = node.metadata_service.component_templates
    if name and name not in tmpls:
        raise ResourceNotFoundException(
            f"component template matching [{name}] not found")
    wanted = [name] if name else sorted(tmpls)
    return 200, {"component_templates": [
        {"name": n, "component_template": tmpls[n]} for n in wanted]}


def delete_component_template(node, params, body, name):
    node.metadata_service.delete_component_template(name)
    return 200, {"acknowledged": True}


def rollover_index(node, params, body, index, new_index=None):
    if new_index is not None:
        body = dict(body or {})
        body["new_index"] = new_index
    dry_run = params.get("dry_run") in ("true", "")
    return 200, node.metadata_service.rollover(index, body, dry_run=dry_run)


def shrink_index(node, params, body, index, target):
    from elasticsearch_tpu.index.metadata import resize_index
    resize_index(node.indices_service, index, target, body, mode="shrink")
    return 200, {"acknowledged": True, "shards_acknowledged": True,
                 "index": target}


def split_index(node, params, body, index, target):
    from elasticsearch_tpu.index.metadata import resize_index
    resize_index(node.indices_service, index, target, body, mode="split")
    return 200, {"acknowledged": True, "shards_acknowledged": True,
                 "index": target}


def clone_index(node, params, body, index, target):
    """ref: RestCloneIndexAction — a same-shard-count resize."""
    from elasticsearch_tpu.index.metadata import resize_index
    resize_index(node.indices_service, index, target, body, mode="clone")
    return 200, {"acknowledged": True, "shards_acknowledged": True,
                 "index": target}


def create_data_stream(node, params, body, name):
    node.metadata_service.create_data_stream(name)
    return 200, {"acknowledged": True}


def get_data_stream(node, params, body, name=None):
    return 200, {"data_streams":
                 node.metadata_service.get_data_streams(name)}


def delete_data_stream(node, params, body, name):
    node.metadata_service.delete_data_stream(name)
    return 200, {"acknowledged": True}


# -- snapshots ---------------------------------------------------------------

def put_repository(node, params, body, repo):
    node.repositories_service.put_repository(repo, body or {})
    return 200, {"acknowledged": True}


def get_repository(node, params, body, repo=None):
    return 200, node.repositories_service.get_configs(repo)


def delete_repository(node, params, body, repo):
    node.repositories_service.delete_repository(repo)
    return 200, {"acknowledged": True}


def create_snapshot(node, params, body, repo, snap):
    import threading
    body = body or {}
    r = node.repositories_service.get_repository(repo)
    index_expr = body.get("indices", "_all")
    if isinstance(index_expr, list):
        index_expr = ",".join(index_expr)
    names = node.indices_service.resolve(index_expr)
    indices = [node.indices_service.get(n) for n in names]

    def run():
        info = r.snapshot(
            snap, indices,
            include_global_state=body.get("include_global_state", True),
            metadata=body.get("metadata"))
        return {"snapshot": info}

    if params.get("wait_for_completion") == "false":
        # accepted-now, result via GET /_tasks/{id} (same contract as
        # the reindex family and the cluster snapshot surface)
        task = node.task_manager.register(
            "transport", "cluster:admin/snapshot/create", cancellable=True)

        def runner():
            try:
                _store_task_result(node, task.id, run())
            except ElasticsearchTpuException as e:
                _store_task_result(node, task.id, {"error": e.to_xcontent()})
            except Exception as e:  # never lose a background failure
                _store_task_result(node, task.id, {"error": {
                    "type": type(e).__name__, "reason": str(e)}})
            finally:
                node.task_manager.unregister(task)

        threading.Thread(target=runner, daemon=True).start()
        return 200, {"accepted": True,
                     "task": f"{node.node_id}:{task.id}"}
    return 200, run()


def get_snapshot(node, params, body, repo, snap):
    r = node.repositories_service.get_repository(repo)
    if snap in ("_all", "*"):
        return 200, {"snapshots": r.list_snapshots()}
    infos = []
    for name in snap.split(","):
        infos.append(r.get_snapshot(name)["info"])
    return 200, {"snapshots": infos}


def delete_snapshot(node, params, body, repo, snap):
    r = node.repositories_service.get_repository(repo)
    for name in snap.split(","):
        r.delete_snapshot(name)
    return 200, {"acknowledged": True}


def snapshot_status(node, params, body, repo, snap):
    """ref: RestSnapshotsStatusAction — per-shard stage + byte stats."""
    r = node.repositories_service.get_repository(repo)
    return 200, {"snapshots": [r.snapshot_status(name)
                               for name in snap.split(",")]}


def restore_snapshot(node, params, body, repo, snap):
    body = body or {}
    r = node.repositories_service.get_repository(repo)
    indices = body.get("indices")
    if isinstance(indices, str):
        indices = indices.split(",")
    result = r.restore(
        snap, node.indices_service, indices=indices,
        rename_pattern=body.get("rename_pattern"),
        rename_replacement=body.get("rename_replacement"))
    return 200, result


def transform_put(node, params, body, id):
    node.transform_service.put_transform(id, body or {})
    return 200, {"acknowledged": True}


def transform_get(node, params, body, id=None):
    return 200, node.transform_service.get_transform(id)


def transform_delete(node, params, body, id):
    node.transform_service.delete_transform(
        id, force=params.get("force") == "true")
    return 200, {"acknowledged": True}


def transform_preview(node, params, body):
    return 200, node.transform_service.preview(body or {})


def transform_start(node, params, body, id):
    node.transform_service.start_transform(id)
    return 200, {"acknowledged": True}


def transform_stop(node, params, body, id):
    node.transform_service.stop_transform(id)
    return 200, {"acknowledged": True}


def transform_stats(node, params, body, id):
    return 200, {"count": 1,
                 "transforms": [node.transform_service.get_stats(id)]}


def transform_schedule_now(node, params, body, id):
    node.transform_service.trigger(id)
    return 200, {"acknowledged": True}


def security_authenticate(node, params, body):
    user = _current_user(node)
    if user is None:
        # security disabled: anonymous superuser view (the reference 401s;
        # with security off there is no authn filter at all)
        return 200, {"username": "_anonymous", "roles": ["superuser"],
                     "enabled": True,
                     "authentication_realm": {"name": "__anonymous",
                                              "type": "anonymous"}}
    out = user.to_dict()
    out["authentication_realm"] = {"name": "default_native", "type": "native"}
    return 200, out


def security_put_user(node, params, body, name):
    r = node.security_service.put_user(name, body or {})
    return 200, r


def security_get_user(node, params, body, name=None):
    return 200, node.security_service.get_user(name)


def security_delete_user(node, params, body, name):
    node.security_service.delete_user(name)
    return 200, {"found": True}


def security_change_password(node, params, body, name):
    node.security_service.change_password(name, (body or {})["password"])
    return 200, {}


def security_put_role(node, params, body, name):
    return 200, node.security_service.put_role(name, body or {})


def security_get_role(node, params, body, name=None):
    return 200, node.security_service.get_role(name)


def security_delete_role(node, params, body, name):
    node.security_service.delete_role(name)
    return 200, {"found": True}


def security_create_token(node, params, body):
    """POST /_security/oauth2/token (ref: RestGetTokenAction)."""
    body = body or {}
    return 200, node.security_service.create_token(
        grant_type=body.get("grant_type", ""),
        username=body.get("username", ""),
        password=body.get("password", ""),
        refresh_token=body.get("refresh_token", ""),
        request_user=_current_user(node))


def security_invalidate_token(node, params, body):
    """DELETE /_security/oauth2/token (ref: RestInvalidateTokenAction)."""
    body = body or {}
    n = node.security_service.invalidate_tokens(
        token=body.get("token"),
        refresh_token=body.get("refresh_token"),
        username=body.get("username"),
        request_user=_current_user(node))
    return 200, {"invalidated_tokens": n, "previously_invalidated_tokens": 0,
                 "error_count": 0}


def security_saml_prepare(node, params, body):
    """POST /_security/saml/prepare (ref:
    RestSamlPrepareAuthenticationAction)."""
    return 200, node.security_service.saml_prepare()


def security_saml_authenticate(node, params, body):
    """POST /_security/saml/authenticate (ref:
    RestSamlAuthenticateAction): {"content": base64 SAMLResponse}."""
    content = (body or {}).get("content", "")
    return 200, node.security_service.saml_authenticate(content)


def security_saml_logout(node, params, body):
    """POST /_security/saml/logout (ref: RestSamlLogoutAction)."""
    return 200, node.security_service.saml_logout(
        (body or {}).get("token", ""))


def _idp(node):
    svc = getattr(node, "idp_service", None)
    if svc is None:
        raise IllegalArgumentException(
            "the identity provider is not enabled (xpack.idp.enabled)")
    return svc


def _unquote_sp(sp_entity_id):
    """SAML entity ids are URLs — the path segment arrives
    percent-encoded."""
    import urllib.parse
    return urllib.parse.unquote(sp_entity_id)


def idp_put_sp(node, params, body, sp_entity_id):
    """PUT /_idp/saml/sp/{sp_entity_id} (ref:
    RestPutSamlServiceProviderAction)."""
    body = body or {}
    sp_entity_id = _unquote_sp(sp_entity_id)
    _idp(node).register_sp(sp_entity_id, body.get("acs", ""),
                           body.get("attributes"))
    return 200, {"service_provider": {"entity_id": sp_entity_id,
                                      "enabled": True}}


def idp_delete_sp(node, params, body, sp_entity_id):
    """DELETE /_idp/saml/sp/{sp_entity_id} (ref:
    RestDeleteSamlServiceProviderAction)."""
    sp_entity_id = _unquote_sp(sp_entity_id)
    found = _idp(node).delete_sp(sp_entity_id)
    if not found:
        raise ResourceNotFoundException(
            f"service provider [{sp_entity_id}] not found")
    return 200, {"service_provider": {"entity_id": sp_entity_id}}


def idp_metadata(node, params, body, sp_entity_id):
    """GET /_idp/saml/metadata/{sp_entity_id} (ref:
    RestSamlMetadataAction)."""
    from elasticsearch_tpu.xpack.saml import SamlException
    try:
        return 200, {"metadata": _idp(node).metadata_xml(
            _unquote_sp(sp_entity_id))}
    except SamlException as e:
        raise ResourceNotFoundException(str(e))


def idp_validate(node, params, body):
    """POST /_idp/saml/validate (ref:
    RestSamlValidateAuthenticationRequestAction)."""
    from elasticsearch_tpu.xpack.saml import SamlException
    try:
        return 200, _idp(node).validate_authn_request(
            (body or {}).get("authn_request", ""))
    except SamlException as e:
        raise IllegalArgumentException(str(e))


def idp_init(node, params, body):
    """POST /_idp/saml/init (ref: RestSamlInitiateSingleSignOnAction):
    issues a signed SAMLResponse for the AUTHENTICATED user to the
    given SP."""
    from elasticsearch_tpu.xpack.saml import SamlException
    body = body or {}
    user = _current_user(node)
    if user is None:
        sec = getattr(node, "security_service", None)
        if sec is not None and sec.enabled:
            raise IllegalArgumentException(
                "SSO initiation requires an authenticated user")
        from elasticsearch_tpu.xpack.security import User
        user = User("_anonymous", [])
    svc = _idp(node)
    try:
        content = svc.issue_response(
            body.get("entity_id", ""), user.username,
            groups=list(user.roles),
            in_response_to=body.get("in_response_to"))
    except SamlException as e:
        raise IllegalArgumentException(str(e))
    return 200, {"post_url": svc.sp_acs(body.get("entity_id", "")),
                 "saml_response": content,
                 "saml_status": "urn:oasis:names:tc:SAML:2.0:"
                                "status:Success"}


def security_delegate_pki(node, params, body):
    """POST /_security/delegate_pki (ref:
    RestDelegatePkiAuthenticationAction)."""
    chain = (body or {}).get("x509_certificate_chain") or []
    return 200, node.security_service.delegate_pki(chain)


def security_put_role_mapping(node, params, body, name):
    return 200, node.security_service.put_role_mapping(name, body or {})


def security_get_role_mapping(node, params, body, name=None):
    return 200, node.security_service.get_role_mappings(name)


def security_delete_role_mapping(node, params, body, name):
    return 200, node.security_service.delete_role_mapping(name)


def security_create_api_key(node, params, body):
    from elasticsearch_tpu.xpack.security import User
    user = _current_user(node) or User("_anonymous", ["superuser"])
    return 200, node.security_service.create_api_key(user, body or {})


def security_builtin_privileges(node, params, body):
    """ref: RestGetBuiltinPrivilegesAction."""
    return 200, {
        "cluster": ["all", "monitor", "manage", "manage_security",
                    "manage_ilm", "manage_ml", "manage_watcher",
                    "manage_transform", "read_ccr", "manage_ccr"],
        "index": ["all", "read", "write", "create", "index", "delete",
                  "manage", "monitor", "view_index_metadata",
                  "create_index", "delete_index"],
    }


def security_get_api_keys(node, params, body):
    return 200, {"api_keys": node.security_service.get_api_keys()}


def security_invalidate_api_key(node, params, body):
    body = body or {}
    key_ids = body.get("ids") or []
    if body.get("id"):
        key_ids = list(key_ids) + [body["id"]]
    out = []
    for kid in key_ids:
        out += node.security_service.invalidate_api_key(key_id=kid)
    if body.get("name"):
        out += node.security_service.invalidate_api_key(
            name=body["name"])
    return 200, {"invalidated_api_keys": out, "error_count": 0}


def ilm_put_policy(node, params, body, id):
    node.ilm_service.put_policy(id, body or {})
    return 200, {"acknowledged": True}


def ilm_get_policy(node, params, body, id=None):
    return 200, node.ilm_service.get_policy(id)


def ilm_delete_policy(node, params, body, id):
    node.ilm_service.delete_policy(id)
    return 200, {"acknowledged": True}


def ilm_status(node, params, body):
    return 200, {"operation_mode": node.ilm_service.status()}


def ilm_start(node, params, body):
    node.ilm_service.start()
    return 200, {"acknowledged": True}


def ilm_stop(node, params, body):
    node.ilm_service.stop()
    return 200, {"acknowledged": True}


def ilm_explain(node, params, body, index):
    out = {}
    for name in node.indices_service.resolve(index):
        out[name] = node.ilm_service.explain(name)
    return 200, {"indices": out}


def ilm_remove(node, params, body, index):
    removed = []
    for name in node.indices_service.resolve(index):
        if node.ilm_service.remove_policy(name):
            removed.append(name)
    return 200, {"has_failures": False, "failed_indexes": [],
                 "removed": removed}


def ilm_retry(node, params, body, index):
    node.ilm_service.retry(index)
    return 200, {"acknowledged": True}


def put_settings(node, params, body, index):
    body = body or {}
    updates = body.get("settings", body)  # both wrapped and flat accepted
    for name in node.indices_service.resolve(index):
        node.indices_service.get(name).update_settings(updates)
    return 200, {"acknowledged": True}


def slm_put_policy(node, params, body, id):
    node.slm_service.put_policy(id, body or {})
    return 200, {"acknowledged": True}


def slm_get_policy(node, params, body, id=None):
    return 200, node.slm_service.get_policies(id)


def slm_delete_policy(node, params, body, id):
    node.slm_service.delete_policy(id)
    return 200, {"acknowledged": True}


def slm_execute_policy(node, params, body, id):
    return 200, node.slm_service.execute_policy(id)


# -- ingest ------------------------------------------------------------------

def put_pipeline(node, params, body, id):
    node.ingest_service.put_pipeline(id, body or {})
    return 200, {"acknowledged": True}


def get_pipeline(node, params, body, id=None):
    pipelines = node.ingest_service.get_pipelines()
    if id is None or id == "*":
        return 200, pipelines
    if id not in pipelines:
        return 404, {}
    return 200, {id: pipelines[id]}


def get_pipelines(node, params, body):
    return 200, node.ingest_service.get_pipelines()


def delete_pipeline(node, params, body, id):
    node.ingest_service.delete_pipeline(id)
    return 200, {"acknowledged": True}


def simulate_pipeline(node, params, body, id=None):
    body = body or {}
    verbose = params.get("verbose") in ("true", "")
    target = id if id is not None else body.get("pipeline", {})
    return 200, node.ingest_service.simulate(
        target, body.get("docs", []), verbose=verbose)


def rank_eval_handler(node, params, body, index):
    body = body or {}

    def search_fn(request_body):
        r = node.search_service.search(index, request_body)
        return [h["_id"] for h in r["hits"]["hits"]]

    result = rank_eval(search_fn, body.get("requests", []),
                       body.get("metric", {"recall": {"k": 10}}))
    return 200, result


# --------------------------------------------------------------------------
# SQL (ref: x-pack/plugin/sql/.../rest/RestSqlQueryAction.java)
# --------------------------------------------------------------------------

def _sql_text_formats(result, fmt):
    cols = result.get("columns", [])
    rows = result.get("rows", [])
    names = [c["name"] for c in cols]
    if fmt in ("csv", "tsv"):
        sep = "," if fmt == "csv" else "\t"
        def esc(v):
            s = "" if v is None else str(v)
            if fmt == "csv" and (sep in s or '"' in s or "\n" in s):
                s = '"' + s.replace('"', '""') + '"'
            return s
        lines = [sep.join(esc(n) for n in names)] if names else []
        lines += [sep.join(esc(v) for v in row) for row in rows]
        return "\n".join(lines)
    # txt: aligned table like the reference's CLI format; continuation
    # pages carry no column headers — rows only
    strs = [[("null" if v is None else str(v)) for v in row]
            for row in rows]
    if not names:
        widths = [max((len(r[j]) for r in strs), default=1)
                  for j in range(len(strs[0]) if strs else 0)]
        out = []
    else:
        widths = [max([len(n)] + [len(r[j]) for r in strs])
                  for j, n in enumerate(names)]
        out = ["|".join(n.ljust(w) for n, w in zip(names, widths)),
               "+".join("-" * w for w in widths)]
    out += ["|".join(v.ljust(w) for v, w in zip(row, widths))
            for row in strs]
    return "\n".join(out)


def sql_query(node, params, body):
    body = dict(body or {})
    if "query" in params and "query" not in body:
        body["query"] = params["query"]
    # mode rides the URL in the reference REST protocol
    # (ref: RestSqlQueryAction — '/_sql?mode=jdbc')
    if "mode" in params and "mode" not in body:
        body["mode"] = params["mode"]
    with node.task_manager.task_scope(
            "transport", "indices:data/read/sql",
            description="sql", cancellable=True):
        result = node.sql_service.query(body)
    fmt = params.get("format", "json")
    if fmt in ("txt", "csv", "tsv"):
        out = {"_cat": _sql_text_formats(result, fmt)}
        if "cursor" in result:
            # text formats return the cursor via the Cursor response
            # header (ref: RestSqlQueryAction text formats)
            out["_headers"] = {"Cursor": result["cursor"]}
        return 200, out
    return 200, result


def sql_translate(node, params, body):
    return 200, node.sql_service.translate(body or {})


def sql_close(node, params, body):
    found = node.sql_service.close_cursor((body or {}).get("cursor", ""))
    return 200, {"succeeded": found}


def eql_search(node, params, body, index):
    with node.task_manager.task_scope(
            "transport", "indices:data/read/eql",
            description=f"indices[{index}]", cancellable=True):
        return 200, node.eql_service.search(index, body or {})


# --------------------------------------------------------------------------
# ML (ref: x-pack/plugin/ml/.../rest/ REST handlers)
# --------------------------------------------------------------------------

def ml_put_job(node, params, body, id):
    job = node.ml_service.put_job(id, body or {})
    return 200, job.config_dict()


def ml_get_job(node, params, body, id):
    job = node.ml_service.get_job(id)
    return 200, {"count": 1, "jobs": [job.config_dict()]}


def ml_get_jobs(node, params, body):
    jobs = [j.config_dict() for j in node.ml_service.jobs.values()]
    return 200, {"count": len(jobs), "jobs": jobs}


def ml_delete_job(node, params, body, id):
    node.ml_service.delete_job(id)
    return 200, {"acknowledged": True}


def ml_open_job(node, params, body, id):
    node.ml_service.open_job(id)
    return 200, {"opened": True}


def ml_close_job(node, params, body, id):
    node.ml_service.close_job(id)
    return 200, {"closed": True}


def ml_model_snapshots(node, params, body, id):
    """GET model_snapshots (ref: RestGetModelSnapshotsAction)."""
    snaps = node.ml_service.model_snapshots(id)
    return 200, {"count": len(snaps), "model_snapshots": snaps}


def ml_revert_snapshot(node, params, body, id, sid):
    """POST _revert (ref: RestRevertModelSnapshotAction)."""
    snap = node.ml_service.revert_model_snapshot(id, sid)
    return 200, {"model": snap}


def ml_post_data(node, params, body, id):
    if isinstance(body, list):
        docs = body
    elif isinstance(body, dict) and body:
        docs = [body]
    else:
        raise IllegalArgumentException("request body is required")
    return 200, node.ml_service.post_data(id, docs)


def ml_get_buckets(node, params, body, id):
    job = node.ml_service.get_job(id)
    buckets = job.buckets
    body = body or {}
    if body.get("anomaly_score") is not None:
        thr = float(body["anomaly_score"])
        buckets = [b for b in buckets if b["anomaly_score"] >= thr]
    return 200, {"count": len(buckets), "buckets": buckets}


def ml_get_records(node, params, body, id):
    job = node.ml_service.get_job(id)
    records = job.records
    body = body or {}
    thr = float(body.get("record_score", 0))
    records = [r for r in records if r["record_score"] >= thr]
    records = sorted(records, key=lambda r: -r["record_score"])
    return 200, {"count": len(records), "records": records}


def ml_put_datafeed(node, params, body, id):
    feed = node.ml_service.put_datafeed(id, body or {})
    return 200, feed.config_dict()


def ml_get_datafeed(node, params, body, id):
    feed = node.ml_service.get_datafeed(id)
    return 200, {"count": 1, "datafeeds": [feed.config_dict()]}


def ml_delete_datafeed(node, params, body, id):
    node.ml_service.delete_datafeed(id)
    return 200, {"acknowledged": True}


def ml_start_datafeed(node, params, body, id):
    body = body or {}
    return 200, node.ml_service.start_datafeed(
        id, start=body.get("start", params.get("start")),
        end=body.get("end", params.get("end")))


def ml_stop_datafeed(node, params, body, id):
    return 200, node.ml_service.stop_datafeed(id)


def ml_put_analytics(node, params, body, id):
    return 200, node.ml_service.put_analytics(id, body or {})


def ml_get_analytics(node, params, body, id):
    cfg = node.ml_service.get_analytics(id)
    return 200, {"count": 1, "data_frame_analytics": [cfg]}


def ml_start_analytics(node, params, body, id):
    return 200, node.ml_service.start_analytics(id)


def ml_put_model(node, params, body, id):
    return 200, node.ml_service.put_trained_model(id, body or {})


def ml_get_model(node, params, body, id):
    m = node.ml_service.get_trained_model(id)
    return 200, {"count": 1, "trained_model_configs": [m]}


def ml_delete_model(node, params, body, id):
    node.ml_service.delete_trained_model(id)
    return 200, {"acknowledged": True}


def ml_infer(node, params, body, id):
    docs = (body or {}).get("docs", [])
    return 200, {"inference_results": node.ml_service.infer(id, docs)}


# --------------------------------------------------------------------------
# rollup / enrich / graph (ref: the corresponding x-pack REST handlers)
# --------------------------------------------------------------------------

def rollup_put_job(node, params, body, id):
    node.rollup_service.put_job(id, body or {})
    return 200, {"acknowledged": True}


def rollup_get_job(node, params, body, id):
    job = node.rollup_service.get_job(id)
    return 200, {"jobs": [{"config": job,
                           "status": {"job_state": job["status"]},
                           "stats": job.get("stats", {})}]}


def rollup_delete_job(node, params, body, id):
    node.rollup_service.delete_job(id)
    return 200, {"acknowledged": True}


def rollup_start_job(node, params, body, id):
    return 200, node.rollup_service.start_job(id)


def rollup_stop_job(node, params, body, id):
    return 200, node.rollup_service.stop_job(id)


def rollup_caps(node, params, body, id):
    return 200, node.rollup_service.caps(id)


def rollup_search(node, params, body, index):
    return 200, node.rollup_service.rollup_search(index, body or {})


def enrich_put_policy(node, params, body, name):
    return 200, node.enrich_service.put_policy(name, body or {})


def enrich_get_policy(node, params, body, name):
    p = node.enrich_service.get_policy(name)
    return 200, {"policies": [{"config": {
        p["type"]: {"name": p["name"], **p["config"]}}}]}


def enrich_list_policies(node, params, body):
    return 200, {"policies": [
        {"config": c} for c in node.enrich_service.list_policies()]}


def enrich_delete_policy(node, params, body, name):
    return 200, node.enrich_service.delete_policy(name)


def enrich_execute_policy(node, params, body, name):
    return 200, node.enrich_service.execute_policy(name)


def graph_explore(node, params, body, index):
    return 200, node.graph_service.explore(index, body or {})


# --------------------------------------------------------------------------
# cluster settings / remote clusters / CCS
# --------------------------------------------------------------------------

def put_cluster_settings(node, params, body):
    body = body or {}
    changed = {}
    for scope in ("persistent", "transient"):
        changed.update(body.get(scope) or {})
    node.persistent_settings.update(changed)
    node.remote_cluster_service.apply_settings(changed)
    return 200, {"acknowledged": True,
                 "persistent": body.get("persistent", {}),
                 "transient": body.get("transient", {})}


def get_cluster_settings(node, params, body):
    return 200, {"persistent": node.persistent_settings, "transient": {}}


_REROUTE_COMMANDS = ("move", "cancel", "allocate_replica")


def cluster_reroute(node, params, body):
    """POST /_cluster/reroute — the allocation-command surface. On the
    single-node REST front there is never another node to move a copy
    to, so every command validates its shape and explains a NO instead
    of pretending to relocate (the multi-node path is
    cluster/node.py reroute → allocation.apply_reroute_commands)."""
    body = body or {}
    explanations = []
    for cmd in body.get("commands", []):
        if not isinstance(cmd, dict) or len(cmd) != 1:
            raise IllegalArgumentException(
                f"malformed reroute command {cmd!r}: expected "
                "{\"move\"|\"cancel\"|\"allocate_replica\": {...}}")
        name, args = next(iter(cmd.items()))
        if name not in _REROUTE_COMMANDS:
            raise IllegalArgumentException(
                f"unknown reroute command [{name}]")
        index = (args or {}).get("index")
        if index is not None:
            node.indices_service.get(index)  # 404 on unknown index
        explanations.append({
            "command": name, "parameters": dict(args or {}),
            "accepted": False,
            "decisions": [{
                "decider": "same_shard", "node": node.node_id,
                "decision": "NO",
                "explanation": "single-node cluster: every copy "
                               "already lives on the only node",
            }],
        })
    resp = {"acknowledged": True}
    if explanations and (str(params.get("explain", "false")).lower()
                         == "true" or
                         str(params.get("dry_run", "false")).lower()
                         == "true"):
        resp["explanations"] = explanations
    return 200, resp


def remote_info(node, params, body):
    return 200, node.remote_cluster_service.info()


def _ccs_search(node, expression, body):
    """Cross-cluster search, ccs_minimize_roundtrips topology (ref:
    TransportSearchAction.ccsRemoteReduce + SearchResponseMerger):
    each cluster reduces independently; hits re-merge here."""
    from elasticsearch_tpu.transport.remote import merge_search_responses
    local, remotes = node.remote_cluster_service.group_indices(expression)
    responses = []
    if local:
        local_expr = ",".join(local)
        lbody = _apply_alias_filter(node, local_expr, body)
        lbody = _apply_dls(node, local_expr, lbody)
        lresp = node.search_service.search(local_expr, lbody)
        responses.append((None, _apply_fls(node, local_expr, lresp)))
    for alias, indices in remotes.items():
        client = node.remote_cluster_service.get_client(alias)
        responses.append(
            (alias, client.search(",".join(indices), body)))
    size = int((body or {}).get("size", 10))
    dirs = []
    for entry in (body or {}).get("sort", []) or []:
        if isinstance(entry, str):
            dirs.append("desc" if entry == "_score" else "asc")
        else:
            (f, spec), = entry.items()
            dirs.append(spec if isinstance(spec, str)
                        else spec.get("order", "asc"))
    merged = merge_search_responses(responses, size=size, sort_dirs=dirs)
    # single-source aggregations pass through untouched
    agg_sources = [r for _, r in responses if r.get("aggregations")]
    if len(agg_sources) == 1:
        merged["aggregations"] = agg_sources[0]["aggregations"]
    return merged


# --------------------------------------------------------------------------
# watcher / monitoring (ref: the corresponding x-pack REST handlers)
# --------------------------------------------------------------------------

def watcher_put(node, params, body, id):
    return 201, node.watcher_service.put_watch(id, body)


def watcher_get(node, params, body, id):
    w = node.watcher_service.get_watch(id)
    return 200, {"_id": id, "found": True, "status": w.status,
                 "watch": w.body_dict()}


def watcher_delete(node, params, body, id):
    return 200, node.watcher_service.delete_watch(id)


def watcher_execute(node, params, body, id):
    body = body or {}
    result = node.watcher_service.execute_watch(
        id, trigger_data=body.get("trigger_data"),
        record=bool(body.get("record_execution", False)),
        alternative_input=body.get("alternative_input"))
    return 200, {"_id": result["_id"], "watch_record": result}


def watcher_activate(node, params, body, id):
    return 200, node.watcher_service.activate(id, True)


def watcher_deactivate(node, params, body, id):
    return 200, node.watcher_service.activate(id, False)


def watcher_stats(node, params, body):
    return 200, node.watcher_service.stats()


def monitoring_bulk(node, params, body):
    docs = body if isinstance(body, list) else [body or {}]
    return 200, node.monitoring_service.bulk(
        params.get("system_id", "external"), docs)


def monitoring_collect(node, params, body):
    """Engine-internal trigger for one collection cycle (tests/ops)."""
    docs = node.monitoring_service.collect_now()
    return 200, {"collected": len(docs)}


# --------------------------------------------------------------------------
# CCR (ref: x-pack/plugin/ccr/.../rest/ REST handlers)
# --------------------------------------------------------------------------

def ccr_follow(node, params, body, index):
    return 200, node.ccr_service.follow(index, body or {})


def ccr_pause(node, params, body, index):
    return 200, node.ccr_service.pause_follow(index)


def ccr_resume(node, params, body, index):
    return 200, node.ccr_service.resume_follow(index)


def ccr_unfollow(node, params, body, index):
    return 200, node.ccr_service.unfollow(index)


def ccr_info(node, params, body, index):
    return 200, node.ccr_service.follow_info(index)


def ccr_stats(node, params, body):
    return 200, node.ccr_service.stats()


def ccr_changes(node, params, body, index):
    body = body or {}
    return 200, node.ccr_service.changes(
        index, int(body.get("from_seq_no", 0)),
        int(body.get("max_operations", 1024)))


def ccr_put_auto_follow(node, params, body, name):
    return 200, node.ccr_service.put_auto_follow(name, body or {})


def ccr_get_auto_follow(node, params, body, name):
    return 200, node.ccr_service.get_auto_follow(name)


def ccr_get_auto_follow_all(node, params, body):
    return 200, node.ccr_service.get_auto_follow()


def ccr_delete_auto_follow(node, params, body, name):
    return 200, node.ccr_service.delete_auto_follow(name)


# --------------------------------------------------------------------------
# index state + searchable snapshots + diagnostics (operational layer)
# --------------------------------------------------------------------------

def close_index(node, params, body, index):
    # idempotent: closing an already-closed index re-acknowledges
    for name in node.indices_service.resolve(index, allow_closed=True):
        idx = node.indices_service.get(name)
        idx.update_settings({"index.state": "close"})
        idx.device_cache.evict(idx._known_seg_names)
    return 200, {"acknowledged": True, "shards_acknowledged": True}


def open_index(node, params, body, index):
    for name in node.indices_service.resolve(index, allow_closed=True):
        node.indices_service.get(name).update_settings(
            {"index.state": "open"})
    return 200, {"acknowledged": True, "shards_acknowledged": True}


def freeze_index(node, params, body, index):
    for name in node.indices_service.resolve(index):
        idx = node.indices_service.get(name)
        idx.update_settings({"index.frozen": True,
                             "index.blocks.write": True})
        idx.device_cache.evict(idx._known_seg_names)
    return 200, {"acknowledged": True, "shards_acknowledged": True}


def unfreeze_index(node, params, body, index):
    for name in node.indices_service.resolve(index):
        node.indices_service.get(name).update_settings(
            {"index.frozen": False, "index.blocks.write": False})
    return 200, {"acknowledged": True, "shards_acknowledged": True}


def mount_snapshot(node, params, body, repo, snap):
    """ref: x-pack searchable-snapshots MountSearchableSnapshotAction —
    a snapshot index mounted read-only with LAZY, cache-backed storage
    (no data files copied at mount time; see
    xpack/searchable_snapshots.py)."""
    from elasticsearch_tpu.xpack import searchable_snapshots as ss
    body = body or {}
    index = body.get("index")
    if not index:
        raise IllegalArgumentException("[index] is required")
    renamed = body.get("renamed_index", index)
    storage = params.get("storage", "full_copy")
    return 200, ss.mount(node, repo, snap, index, renamed,
                         storage=storage)


def searchable_snapshot_stats(node, params, body):
    from elasticsearch_tpu.xpack import searchable_snapshots as ss
    indices = {}
    for name in node.indices_service.indices:
        idx = node.indices_service.get(name)
        if str(idx.settings.get("index.store.type", "")) == "snapshot":
            indices[name] = {
                "repository": idx.settings.get(
                    "index.store.snapshot.repository_name"),
                "snapshot": idx.settings.get(
                    "index.store.snapshot.snapshot_name"),
                "storage": idx.settings.get(
                    "index.store.snapshot.storage", "full_copy"),
            }
    cache = ss.node_cache(node.data_path)
    return 200, {"total": len(indices), "indices": indices,
                 "shared_cache": cache.stats()}


def hot_threads(node, params, body):
    """ref: monitor/jvm/HotThreads.java — node occupancy report. The
    schedulable unit here is the registered TASK (transport/tasks.py),
    so the report is the top running tasks with their running time (on
    the scheduler clock) and CURRENT profile stage — a long-running
    search shows `launch`/`fetch`/`aggs.collect`, which is the
    diagnostic the reference's thread dump provides. ``threads`` caps
    the per-node task count (default 3, ES parity)."""
    from elasticsearch_tpu.transport.tasks import hot_threads_text
    limit = int(params.get("threads", 3))
    return 200, {"_cat": hot_threads_text(
        node.task_manager, node.name, node.node_id, limit=limit)}


def deprecations(node, params, body):
    """ref: x-pack deprecation plugin — settings/mapping checks."""
    cluster_issues = []
    index_issues = {}
    for name in node.indices_service.indices:
        idx = node.indices_service.get(name)
        issues = []
        if idx.is_frozen:
            issues.append({
                "level": "warning",
                "message": "frozen indices are deprecated",
                "details": "use searchable snapshots or the cold tier "
                           "instead of freezing indices",
                "url": "https://ela.st/es-deprecation-7-frozen-index"})
        if issues:
            index_issues[name] = issues
    return 200, {"cluster_settings": cluster_issues,
                 "node_settings": [],
                 "index_settings": index_issues,
                 "ml_settings": []}


def _autoscaling_store(node) -> Dict[str, Dict[str, Any]]:
    """Per-node persisted policy store (ref: autoscaling policies live in
    cluster state)."""
    import os
    if not hasattr(node, "autoscaling_policies"):
        path = os.path.join(node.data_path, "_autoscaling.json")
        policies = {}
        if os.path.exists(path):
            with open(path) as fh:
                policies = json.load(fh)
        node.autoscaling_policies = policies
        node._autoscaling_path = path
    return node.autoscaling_policies


def _autoscaling_persist(node):
    import os
    tmp = node._autoscaling_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(node.autoscaling_policies, fh)
    os.replace(tmp, node._autoscaling_path)


def autoscaling_put(node, params, body, name):
    _autoscaling_store(node)[name] = body or {}
    _autoscaling_persist(node)
    return 200, {"acknowledged": True}


def autoscaling_get(node, params, body, name):
    store = _autoscaling_store(node)
    if name not in store:
        raise ResourceNotFoundException(
            f"autoscaling policy with name [{name}] does not exist")
    return 200, {name: {"policy": store[name]}}


def autoscaling_delete(node, params, body, name):
    store = _autoscaling_store(node)
    if name not in store:
        raise ResourceNotFoundException(
            f"autoscaling policy with name [{name}] does not exist")
    del store[name]
    _autoscaling_persist(node)
    return 200, {"acknowledged": True}


def autoscaling_capacity(node, params, body):
    """ref: x-pack autoscaling GetAutoscalingCapacityAction — observed
    usage drives the required capacity decision."""
    total_docs = 0
    storage = 0
    for name in node.indices_service.indices:
        idx = node.indices_service.get(name)
        s = idx.stats()
        total_docs += s["docs"]["count"]
        storage += s.get("store", {}).get("size_in_bytes", 0)
    policies = {}
    for pname in _autoscaling_store(node):
        policies[pname] = {
            "required_capacity": {"total": {
                "storage": int(storage * 1.25),
                "memory": int(storage * 0.1)}},
            "current_capacity": {"total": {"storage": storage}},
            "current_nodes": [{"name": node.name}],
            "deciders": {"observed_usage": {
                "required_capacity": {"total": {
                    "storage": int(storage * 1.25)}}}},
        }
    return 200, {"policies": policies}


# --------------------------------------------------------------------------
# node shutdown (ref: x-pack shutdown plugin — single-node flavour; the
# cluster plane lives on ClusterNode's NODE_SHUTDOWN_* transport actions)
# --------------------------------------------------------------------------

def _shutdown_store(node) -> Dict[str, Dict[str, Any]]:
    """Per-node persisted shutdown-marker store (cluster-state metadata
    in the multi-node plane)."""
    import os
    if not hasattr(node, "node_shutdowns"):
        path = os.path.join(node.data_path, "_node_shutdown.json")
        markers = {}
        if os.path.exists(path):
            with open(path) as fh:
                markers = json.load(fh)
        node.node_shutdowns = markers
        node._node_shutdown_path = path
    return node.node_shutdowns


def _shutdown_persist(node) -> None:
    with open(node._node_shutdown_path, "w") as fh:
        json.dump(node.node_shutdowns, fh)


def _describe_single_node_shutdown(marker: Dict[str, Any]
                                   ) -> Dict[str, Any]:
    from elasticsearch_tpu.cluster.state import (
        SHUTDOWN_COMPLETE, SHUTDOWN_REMOVE, SHUTDOWN_STALLED)
    # one-box semantics: a `restart` has nothing to drain (COMPLETE);
    # a `remove` has no peer to drain to, so it reports STALLED — the
    # honest answer, matching the multi-node status vocabulary
    status = (SHUTDOWN_STALLED if marker["type"] == SHUTDOWN_REMOVE
              else SHUTDOWN_COMPLETE)
    return {**marker, "status": status,
            "shard_migration": {"status": status}}


def put_node_shutdown(node, params, body, node_id):
    from elasticsearch_tpu.cluster.shutdown import (
        DEFAULT_SHUTDOWN_DELAY_S, VALID_SHUTDOWN_TYPES, parse_time_s)
    body = body or {}
    sd_type = body.get("type")
    if sd_type not in VALID_SHUTDOWN_TYPES:
        raise IllegalArgumentException(
            f"invalid shutdown type [{sd_type}]; must be one of "
            f"{sorted(VALID_SHUTDOWN_TYPES)}")
    if node_id != node.node_id:
        raise ResourceNotFoundException(
            f"node [{node_id}] not found in cluster")
    delay_s = parse_time_s(body.get("allocation_delay"))
    import time
    _shutdown_store(node)[node_id] = {
        "node_id": node_id, "type": sd_type,
        "reason": body.get("reason", ""),
        "shutdown_started": time.time(),
        "allocation_delay": (DEFAULT_SHUTDOWN_DELAY_S
                             if delay_s is None else delay_s),
    }
    _shutdown_persist(node)
    return 200, {"acknowledged": True}


def get_node_shutdown(node, params, body, node_id):
    store = _shutdown_store(node)
    if node_id not in store:
        raise ResourceNotFoundException(
            f"no shutdown marker for node [{node_id}]")
    return 200, {"nodes": {
        node_id: _describe_single_node_shutdown(store[node_id])}}


def get_all_node_shutdowns(node, params, body):
    store = _shutdown_store(node)
    return 200, {"nodes": {
        nid: _describe_single_node_shutdown(m)
        for nid, m in sorted(store.items())}}


def delete_node_shutdown(node, params, body, node_id):
    store = _shutdown_store(node)
    if node_id not in store:
        raise ResourceNotFoundException(
            f"no shutdown marker for node [{node_id}]")
    del store[node_id]
    _shutdown_persist(node)
    return 200, {"acknowledged": True}


# --------------------------------------------------------------------------
# extended _cat family (ref: rest/action/cat/)
# --------------------------------------------------------------------------

def cat_nodes(node, params, body):
    import resource
    from elasticsearch_tpu.transport.transport import CURRENT_VERSION
    ru = resource.getrusage(resource.RUSAGE_SELF)
    # ip heap.mb version node.role master name — the wire-version
    # column is what an operator watches during a rolling upgrade
    return 200, {"_cat": (
        f"127.0.0.1 {int(ru.ru_maxrss / 1024)} v{CURRENT_VERSION} "
        f"dimr * {node.name}")}


def cat_master(node, params, body):
    return 200, {"_cat": f"{node.node_id} 127.0.0.1 127.0.0.1 {node.name}"}


def cat_allocation(node, params, body):
    n_shards = sum(node.indices_service.get(n).num_shards
                   for n in node.indices_service.indices)
    return 200, {"_cat": f"{n_shards} 127.0.0.1 127.0.0.1 {node.name}"}


def cat_templates(node, params, body):
    lines = []
    for name, t in node.metadata_service.index_templates.items():
        patterns = ",".join(t.get("index_patterns", []))
        lines.append(f"{name} [{patterns}] {t.get('priority', 0)}")
    return 200, {"_cat": "\n".join(lines)}


def cat_thread_pool(node, params, body):
    """name pool active queue rejected (ref: RestThreadPoolAction) —
    from the real named executors."""
    rows = []
    for name, st in sorted(node.threadpool.stats().items()):
        rows.append(f"{node.name} {name} {st['active']} {st['queue']} "
                    f"{st['rejected']}")
    return 200, {"_cat": "\n".join(rows)}


def cat_ml_jobs(node, params, body):
    rows = []
    for job_id, job in sorted(node.ml_service.jobs.items()):
        rows.append(f"{job_id} {job.state} {job.processed_record_count} "
                    f"{len(job.buckets)}")
    return 200, {"_cat": "\n".join(rows)}


def cat_ml_datafeeds(node, params, body):
    rows = [f"{fid} {feed.state}" for fid, feed in
            sorted(node.ml_service.datafeeds.items())]
    return 200, {"_cat": "\n".join(rows)}


def cat_ml_trained_models(node, params, body):
    rows = [f"{mid} {m.get('model_type', 'lang_ident')}" for mid, m in
            sorted(node.ml_service.trained_models.items())]
    return 200, {"_cat": "\n".join(rows)}


def cat_transforms(node, params, body):
    rows = []
    svc = node.transform_service
    for tid in sorted(svc._configs):
        state = svc._stats.get(tid, {}).get("state", "stopped")
        rows.append(f"{tid} {state}")
    return 200, {"_cat": "\n".join(rows)}


def cat_fielddata(node, params, body):
    """ref: RestFielddataAction. Doc values live in device HBM segments
    here (no on-heap fielddata cache), so per-field bytes are the HBM
    numeric/keyword column sizes."""
    rows = []
    cache = node.indices_service.device_cache
    for name, idx in sorted(node.indices_service.indices.items()):
        for searcher in idx.shard_searchers():
            for seg in searcher.segments:
                dev = cache.get(seg)
                for f, arr in sorted(dev.numerics.items()):
                    rows.append(f"{node.name} {f} {arr.nbytes}")
    return 200, {"_cat": "\n".join(rows)}


def cat_pending_tasks(node, params, body):
    """GET /_cat/pending_tasks — rendered from the same master-service
    queue `_cluster/pending_tasks` reads."""
    lines = [f"{t['insert_order']} {t['time_in_queue_millis']}ms "
             f"{t['priority']} {t['source']}"
             for t in _pending_cluster_tasks(node)]
    return 200, {"_cat": "\n".join(lines)}


def cat_segments(node, params, body):
    lines = []
    for name in sorted(node.indices_service.indices):
        idx = node.indices_service.get(name)
        for si, shard in enumerate(idx.shards):
            for seg in shard.segments:
                lines.append(f"{name} {si} p 127.0.0.1 {seg.name} "
                             f"{seg.n_docs} {int(seg.live.sum())}")
    return 200, {"_cat": "\n".join(lines)}


def _recovery_entries(node, index=None):
    """Per-shard recovery states of this single node, in the same shape
    the cluster's RecoveryState.to_dict emits (cluster/data_node.py).
    Every local shard here recovered from its own store at open —
    `local_store`, stage DONE — with honest numbers: bytes actually on
    disk, ops actually sitting in the translog, segments actually
    resident in HBM right now."""
    entries = []
    for name in sorted(node.indices_service.indices):
        if index is not None and name != index:
            continue
        idx = node.indices_service.get(name)
        cache = getattr(idx, "device_cache", None) or \
            node.indices_service.device_cache
        resident = getattr(cache, "_cache", {})
        for si, engine in enumerate(idx.shards):
            # count ops BEFORE sizing the directory: read_ops syncs the
            # in-memory translog buffer to disk as a side effect
            n_ops = len(engine.translog.read_ops(1))
            nbytes = 0
            for root, _dirs, fnames in os.walk(engine.path):
                for fname in fnames:
                    try:
                        nbytes += os.path.getsize(
                            os.path.join(root, fname))
                    except OSError:
                        continue
            hbm_segments = [seg for seg in engine.segments
                            if seg.name in resident]
            hbm_bytes = 0
            for seg in hbm_segments:
                entry = resident.get(seg.name)
                if entry is not None:
                    hbm_bytes += entry[1].hbm_bytes()
            entries.append({
                "index": name,
                "shard_id": si,
                "allocation_id": None,
                "type": "local_store",
                "protocol": 0,
                "stage": "DONE",
                "source_node": node.name,
                "target_node": node.name,
                "index_files": {"total_bytes": nbytes,
                                "recovered_bytes": nbytes},
                "translog": {"ops_replayed": n_ops},
                "device": {"hbm_uploaded_bytes": hbm_bytes,
                           "hbm_segments": len(hbm_segments),
                           "hbm_skipped_segments": 0},
                "start_time": None,
                "stop_time": None,
                "total_time_ms": None,
                "task_id": None,
                "failure": None,
            })
    return entries


def indices_recovery(node, params, body):
    """GET /_recovery — recovery states grouped by index."""
    out = {}
    for rec in _recovery_entries(node):
        out.setdefault(rec["index"], {"shards": []})["shards"].append(rec)
    return 200, out


def index_recovery(node, params, body, index):
    """GET /{index}/_recovery."""
    node.indices_service.get(index)  # 404 on unknown index
    shards = _recovery_entries(node, index=index)
    if not shards:
        return 200, {}
    return 200, {index: {"shards": shards}}


def cat_recovery(node, params, body):
    """GET /_cat/recovery — one row per shard copy, rendered from the
    same entries `/_recovery` serves: index shard time type stage
    source_node target_node bytes ops."""
    lines = []
    for rec in _recovery_entries(node):
        time_ms = rec["total_time_ms"]
        lines.append(
            f"{rec['index']} {rec['shard_id']} "
            f"{0 if time_ms is None else int(time_ms)}ms "
            f"{rec['type']} {rec['stage'].lower()} "
            f"{rec['source_node']} {rec['target_node']} "
            f"{rec['index_files']['recovered_bytes']} "
            f"{rec['translog']['ops_replayed']}")
    return 200, {"_cat": "\n".join(lines)}


def cat_repositories(node, params, body):
    return 200, {"_cat": "\n".join(
        f"{name} fs" for name in sorted(
            node.repositories_service.get_configs(None)))}


def cat_snapshots(node, params, body, repo):
    """ref: RestSnapshotAction default columns: id status start_epoch
    end_epoch duration indices successful_shards failed_shards
    total_shards (the repository is the path param, not a column)."""
    r = node.repositories_service.get_repository(repo)
    lines = []
    for s in r.list_snapshots():
        start = s.get("start_time_in_millis", 0)
        end = s.get("end_time_in_millis", 0)
        duration_s = max(0, end - start) // 1000 if end else 0
        shards = s.get("shards", {}) or {}
        lines.append(
            f"{s['snapshot']} {s.get('state', 'SUCCESS')} "
            f"{start // 1000} {end // 1000} {duration_s}s "
            f"{len(s.get('indices', []))} "
            f"{shards.get('successful', 0)} {shards.get('failed', 0)} "
            f"{shards.get('total', 0)}")
    return 200, {"_cat": "\n".join(lines)}


def cat_tasks(node, params, body):
    """GET /_cat/tasks — rendered through the `_tasks` fan-out shape
    (transport/tasks.py render_cat_tasks), so the text surface shows
    the same node-attributed rows the cluster fan-out produces."""
    from elasticsearch_tpu.transport.tasks import render_cat_tasks
    return 200, {"_cat": render_cat_tasks(
        _node_task_infos(node, actions=params.get("actions")))}


def cat_plugins(node, params, body):
    """GET /_cat/plugins (ref: rest/action/cat/RestPluginsAction).
    Bundled x-pack modules plus installed plugins."""
    mods = ["sql", "eql", "ml", "watcher", "monitoring", "rollup",
            "enrich", "graph", "ccr", "transform", "ilm", "security",
            "async-search", "searchable-snapshots", "autoscaling"]
    rows = [f"{node.name} {m} {__version__}" for m in sorted(mods)]
    rows += [f"{node.name} {p['name']} - {p['classname']}"
             for p in node.plugins_service.info()]
    return 200, {"_cat": "\n".join(rows)}


def cat_nodeattrs(node, params, body):
    return 200, {"_cat": f"{node.name} 127.0.0.1 127.0.0.1 - -"}


def add_voting_exclusions(node, params, body):
    """POST /_cluster/voting_config_exclusions (ref:
    RestAddVotingConfigExclusionAction). On the single-node container
    there is no multi-node voting configuration to amend — excluding the
    only master is rejected exactly as the reference refuses to exclude
    ALL master-eligible nodes; the Coordinator-level API
    (cluster/coordination.py) implements the real semantics for
    clusters."""
    names = [n for n in params.get(
        "node_names", params.get("node_ids", "")).split(",") if n]
    if not names:
        raise IllegalArgumentException(
            "add voting config exclusions requests must specify at "
            "least one node")
    if node.name in names or node.node_id in names:
        return 400, {"error": {
            "type": "illegal_argument_exception",
            "reason": "add voting config exclusions request for "
                      f"{names} would leave no master-eligible voting "
                      "nodes in the cluster"}, "status": 400}
    return 200, {"acknowledged": True}


def clear_voting_exclusions(node, params, body):
    return 200, {"acknowledged": True}


def allocation_explain(node, params, body):
    """GET/POST /_cluster/allocation/explain (ref:
    TransportClusterAllocationExplainAction) — single-node form: every
    shard of an existing index is assigned locally."""
    body = body or {}
    index = body.get("index")
    if index is None:
        # unparameterized: explain the first shard found (the reference
        # picks the first unassigned shard; with none unassigned here,
        # any shard serves)
        names = sorted(node.indices_service.indices)
        if not names:
            raise IllegalArgumentException(
                "unable to find any unassigned shards to explain")
        index = names[0]
    idx = node.indices_service.get(index)
    shard = int(body.get("shard", 0))
    if shard >= idx.num_shards:
        raise IllegalArgumentException(
            f"shard [{shard}] does not exist for index [{index}]")
    return 200, {
        "index": index,
        "shard": shard,
        "primary": bool(body.get("primary", True)),
        "current_state": "started",
        "current_node": {"id": node.node_id, "name": node.name},
        "can_remain_on_current_node": "yes",
        "can_rebalance_cluster": "no",
        "can_rebalance_cluster_decisions": [{
            "decider": "single_node",
            "decision": "NO",
            "explanation": "a single-node cluster has no rebalance "
                           "targets"}],
    }


def reload_secure_settings(node, params, body):
    """POST /_nodes/reload_secure_settings — re-read the keystore from
    disk (ref: action/admin/cluster/node/reload/
    TransportNodesReloadSecureSettingsAction). Accepts an optional
    {"secure_settings_password": "..."} body."""
    password = (body or {}).get("secure_settings_password",
                                os.environ.get("ES_KEYSTORE_PASSPHRASE", ""))
    result = {"name": node.name, "reload_exception": None}
    if node.keystore is not None:
        try:
            node.keystore.load(password)
        except Exception as e:   # noqa: BLE001 — reported per-node, as ref
            result["reload_exception"] = {
                "type": type(e).__name__, "reason": str(e)}
    return 200, {
        "_nodes": {"total": 1, "successful":
                   0 if result["reload_exception"] else 1, "failed":
                   1 if result["reload_exception"] else 0},
        "cluster_name": node.cluster_name,
        "nodes": {node.node_id: result},
    }


def nodes_info(node, params, body):
    """GET /_nodes — node identity/roles/transport info (ref:
    action/admin/cluster/node/info/TransportNodesInfoAction)."""
    import platform
    import sys as _sys
    return 200, {
        "_nodes": {"total": 1, "successful": 1, "failed": 0},
        "cluster_name": node.cluster_name,
        "nodes": {node.node_id: {
            "name": node.name,
            "transport_address": "127.0.0.1:9300",
            "host": "127.0.0.1",
            "ip": "127.0.0.1",
            "version": __version__,
            "roles": ["master", "data", "ingest", "ml", "transform"],
            "os": {"name": platform.system(),
                   "arch": platform.machine()},
            "process": {"id": os.getpid() if hasattr(os, "getpid") else 0},
            "settings": {"node": {"name": node.name}},
        }},
    }


# --------------------------------------------------------------------------
# term vectors (ref: action/termvectors/TransportTermVectorsAction — here
# recomputed from _source through the field's analyzer, the same strategy
# the reference uses when vectors are not stored)
# --------------------------------------------------------------------------

def _termvectors_for(node, index, doc_id, body,
                     routing: Optional[str] = None):
    body = body or {}
    if doc_id is None:
        return {"_index": index, "_id": None, "found": False,
                "error": {"type": "illegal_argument_exception",
                          "reason": "[_id] is required"}}
    # aliases/data streams resolve like every other doc endpoint
    index = node.metadata_service.write_target(index)
    idx = node.indices_service.get(index)
    result = idx.get_doc(doc_id, routing=body.get("routing", routing))
    if result is None or not getattr(result, "found", True):
        return {"_index": index, "_id": doc_id, "found": False}
    source = result.source if hasattr(result, "source") else result
    if source is None:
        return {"_index": index, "_id": doc_id, "found": False}
    fields = body.get("fields")
    want_term_stats = bool(body.get("term_statistics", False))
    tv: Dict[str, Any] = {}
    from elasticsearch_tpu.search.context import ShardStats
    stats = ShardStats([seg for shard in idx.shards
                        for seg in shard.segments])
    analysis = idx.mapper.mapper.analysis
    for fname, ft in idx.mapper.mapper.fields.items():
        if ft.type_name != "text":
            continue
        if fields and fname not in fields:
            continue
        value = source.get(fname) if isinstance(source, dict) else None
        if value is None:
            continue
        name = getattr(ft, "analyzer_name", "standard")
        try:
            analyzer = analysis.get(name)
        except Exception:
            analyzer = analysis.get("standard")   # indexing's fallback
        # arrays analyze per value with the indexing chain's position gap
        values = value if isinstance(value, list) else [value]
        terms: Dict[str, Any] = {}
        pos_base = 0
        for v in values:
            max_pos = -1
            for tok in analyzer.analyze(str(v)):
                entry = terms.setdefault(tok.term, {"term_freq": 0,
                                                    "tokens": []})
                entry["term_freq"] += 1
                entry["tokens"].append({
                    "position": pos_base + tok.position,
                    "start_offset": tok.start_offset,
                    "end_offset": tok.end_offset})
                max_pos = max(max_pos, pos_base + tok.position)
            pos_base = max_pos + 100        # the multi-value gap
        if want_term_stats:
            for term, entry in terms.items():
                entry["doc_freq"] = stats.doc_freq(fname, term)
        if terms:
            n_docs, _ = stats.field_stats(fname)
            tv[fname] = {
                "field_statistics": {"doc_count": n_docs},
                "terms": terms,
            }
    return {"_index": index, "_id": doc_id, "found": True,
            "term_vectors": tv}


def termvectors(node, params, body, index, id):
    body = dict(body or {})
    if "fields" in params and "fields" not in body:
        body["fields"] = params["fields"].split(",")
    if params.get("term_statistics") in ("true", ""):
        body["term_statistics"] = True
    return 200, _termvectors_for(node, index, id, body,
                                 routing=params.get("routing"))


def mtermvectors(node, params, body, index):
    body = body or {}
    out = []

    def one(target_index, doc_id, spec):
        # per-doc failures become error entries, never request failures
        try:
            return _termvectors_for(node, target_index, doc_id, spec)
        except ElasticsearchTpuException as e:
            return {"_index": target_index, "_id": doc_id,
                    "found": False, "error": e.to_xcontent()}

    for spec in body.get("docs", []):
        out.append(one(spec.get("_index", index), spec.get("_id"), spec))
    for doc_id in body.get("ids", []):
        out.append(one(index, doc_id, body))
    return 200, {"docs": out}


def _license_dict(node) -> Dict[str, Any]:
    """One license source for /_license and /_xpack (they must agree)."""
    return {"status": "active", "uid": node.node_id, "type": "basic",
            "mode": "basic", "issue_date_in_millis": 0, "max_nodes": 1000,
            "issued_to": node.cluster_name, "issuer": "elasticsearch_tpu",
            "start_date_in_millis": -1}


def xpack_info(node, params, body):
    """GET /_xpack — feature availability (ref: XPackInfoAction); every
    feature ships enabled under the basic license here."""
    features = ["analytics", "async_search", "autoscaling", "ccr", "enrich",
                "eql", "frozen_indices", "graph", "ilm", "logstash", "ml",
                "monitoring", "rollup", "searchable_snapshots", "security",
                "slm", "sql", "transform", "voting_only", "watcher"]
    lic = _license_dict(node)
    return 200, {
        "build": {"date": "2026-01-01T00:00:00.000Z"},
        "license": {k: lic[k] for k in ("uid", "type", "mode", "status")},
        "features": {f: {"available": True,
                         "enabled": (f != "security"
                                     or node.security_service.enabled)}
                     for f in features},
    }


def license_info(node, params, body):
    return 200, {"license": _license_dict(node)}
