"""Shared CLI plumbing for component binaries (component-base analog:
staging/src/k8s.io/component-base cli flags/logs; option pattern of
cmd/kube-scheduler/app/options)."""

from __future__ import annotations

import argparse
import json
import signal
import threading
from typing import Optional, Tuple


def add_common_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument(
        "--config", metavar="FILE",
        help="component configuration file (KubeSchedulerConfiguration JSON)",
    )
    p.add_argument(
        "--platform", default=None, choices=("cpu", "tpu"),
        help="force the jax platform (cpu = 8 virtual host devices; "
        "default keeps the environment's backend)",
    )
    p.add_argument("-v", "--verbosity", type=int, default=0,
                   help="log level (klog.V analog)")


def tls_client_context(cacert: Optional[str] = None,
                       client_cert: Optional[str] = None,
                       client_key: Optional[str] = None):
    """ssl context for an HTTPS plane: trust ``cacert`` (or the
    KTPU_CACERT env var — the kubeconfig certificate-authority analog
    every CLI inherits), optionally presenting a client cert."""
    import os as _os
    import ssl as _ssl

    cacert = cacert or _os.environ.get("KTPU_CACERT", "")
    if cacert:
        # pinned cluster CA: hostname verification STAYS on (ADVICE r4
        # medium — kubeadm init issues the serving cert with IP/DNS SANs
        # for host/127.0.0.1/localhost/kubernetes*, and Python ssl matches
        # IP SANs, so relaxing here would accept ANY cert the cluster CA
        # signed for ANY address).  Reach planes by a SAN'd address or add
        # the address to the serving cert's SANs.
        ctx = _ssl.create_default_context(cafile=cacert)
    elif _os.environ.get("KTPU_INSECURE_SKIP_TLS_VERIFY", "") == "1":
        ctx = _ssl._create_unverified_context()
    else:
        # no pinned CA: full public-trust verification INCLUDING hostname
        # (disabling it here would accept any publicly-issued cert for
        # any name — a silent MITM downgrade); hostname relaxation is
        # only sound in the pinned-private-CA branch above
        ctx = _ssl.create_default_context()
    cert = client_cert or _os.environ.get("KTPU_CLIENT_CERT", "")
    key = client_key or _os.environ.get("KTPU_CLIENT_KEY", "")
    if cert and key:
        ctx.load_cert_chain(certfile=cert, keyfile=key)
    return ctx


def tls_urlopen(req, timeout: float):
    """urlopen with the process-wide TLS trust for https URLs (the ONE
    client-transport policy point: api_request, RemoteCluster, and the
    reflector all route through here)."""
    import urllib.request as _ur

    url = req.full_url if hasattr(req, "full_url") else str(req)
    ctx = tls_client_context() if url.startswith("https://") else None
    return _ur.urlopen(req, timeout=timeout, context=ctx)


def api_request(server: str, method: str, path: str, payload=None,
                token: Optional[str] = None) -> dict:
    """One HTTP helper for every CLI: JSON in/out, HTTP errors surfaced as
    Status dicts (body preserved), unreachable server as a 503 Status.
    ``token`` adds an ``Authorization: Bearer`` header (RBAC'd planes);
    https servers verify against KTPU_CACERT (see tls_client_context)."""
    import json as _json
    import urllib.error
    import urllib.request

    data = _json.dumps(payload).encode() if payload is not None else None
    headers = {"Content-Type": "application/json"}
    if token:
        headers["Authorization"] = f"Bearer {token}"
    req = urllib.request.Request(
        server.rstrip("/") + path, data=data, method=method,
        headers=headers,
    )
    try:
        with tls_urlopen(req, timeout=30) as resp:
            return _json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as e:
        body = e.read().decode(errors="replace")
        try:
            return _json.loads(body)
        except ValueError:
            return {"kind": "Status", "code": e.code, "message": body}
    except urllib.error.URLError as e:
        return {"kind": "Status", "code": 503, "reason": "Unreachable",
                "message": f"cannot reach apiserver {server}: {e.reason}"}


def parse_hostport(addr: str, default_port: int) -> Tuple[str, int]:
    """'0.0.0.0:10251' / ':10251' / '10251' -> (host, port)."""
    if ":" in addr:
        host, _, port = addr.rpartition(":")
        return host or "0.0.0.0", int(port or default_port)
    return "0.0.0.0", int(addr or default_port)


def apply_platform(platform: Optional[str], verbosity: int = 0) -> None:
    """--platform cpu: the 8-virtual-device cpu mesh, set through
    jax.config before the first backend touch (tests/conftest.py recipe);
    any other value leaves the platform JAX finds.  Also initializes the
    leveled logger (the component-base logs.go init step)."""
    from kubernetes_tpu.utils import klog

    klog.set_verbosity(verbosity)
    if platform == "cpu":
        from kubernetes_tpu.utils.jaxenv import force_cpu_mesh

        force_cpu_mesh(8)


def load_component_config(path: Optional[str]):
    from kubernetes_tpu.config.types import KubeSchedulerConfiguration

    if path:
        return KubeSchedulerConfiguration.from_file(path)
    return KubeSchedulerConfiguration()


def wait_for_term(stop_event: Optional[threading.Event] = None) -> None:
    """Block until SIGINT/SIGTERM (the stopCh pattern)."""
    ev = stop_event or threading.Event()

    def handler(signum, frame):
        ev.set()

    signal.signal(signal.SIGINT, handler)
    signal.signal(signal.SIGTERM, handler)
    ev.wait()


def build_wired_scheduler(cluster, cc=None, use_informers: bool = False):
    """One shared recipe for embedding a scheduler against a LocalCluster
    (the server.go:164-201 build + AddAllEventHandlers): component config
    honored when given.  use_informers routes events through the shared
    informer stack (reflector->DeltaFIFO->indexed store->handlers) the
    way cmd/kube-scheduler does — the right mode against a remote
    mirror; the direct wiring avoids the extra thread for embedded use."""
    from kubernetes_tpu.runtime.cache import SchedulerCache
    from kubernetes_tpu.runtime.cluster import (
        make_cluster_binder,
        wire_scheduler,
    )
    from kubernetes_tpu.runtime.queue import PriorityQueue
    from kubernetes_tpu.runtime.scheduler import Scheduler, SchedulerConfig

    cfg = (
        SchedulerConfig.from_component_config(cc)
        if cc is not None else SchedulerConfig()
    )
    sched = Scheduler(
        cache=SchedulerCache(), queue=PriorityQueue(),
        binder=make_cluster_binder(cluster), config=cfg,
    )
    if use_informers:
        from kubernetes_tpu.client.informer import (
            SharedInformerFactory,
            wire_scheduler_informers,
        )

        factory = SharedInformerFactory(cluster)
        wire_scheduler_informers(factory, sched)
        factory.start()
        factory.wait_for_cache_sync(30.0)
        sched.informer_factory = factory  # teardown handle
    else:
        wire_scheduler(cluster, sched)
    return sched
