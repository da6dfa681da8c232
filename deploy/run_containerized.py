#!/usr/bin/env python
"""Execute the deploy manifests' container semantics in local Linux
namespaces — C15 execution evidence on an image with no docker daemon
and no cluster.

What `docker build` + `kubectl apply` would prove, decomposed into what
THIS environment can actually execute versus what it cannot:

executed here (real, not simulated):
- the Dockerfile runtime-stage layout is assembled as a rootfs: COPY
  semantics for ``/app/split_learning_tpu``, the
  builder-stage native-codec precompile into ``/app/native-cache``,
  the Dockerfile's ENV block, ``USER appuser`` (uid 1000, non-root),
  ``WORKDIR /app``, a writable ``/ckpt`` standing in for the PVC
  (host binds remounted read-only, except /dev);
- the server Deployment's EXACT ``command:`` (parsed from
  deploy/split-learning.yaml, never retyped) runs chrooted into that
  rootfs under fresh mount/PID/UTS namespaces as uid 1000;
- the Job's init-container readiness barrier (``until curl /health``)
  and the readinessProbe's path/port are exercised against it;
- the client Job's EXACT ``command:`` runs in a second container of
  the same image and must exit 0 with a dropping loss.

cannot be executed here (and is NOT simulated):
- pulling ``python:3.11-slim`` (zero egress): the host interpreter and
  libraries are bind-mounted read-only in its place;
- k8s Service DNS (``split-server``): rewritten to 127.0.0.1, both
  containers sharing the host network namespace — the DNS/selector/
  port wiring stays covered by tests/test_deploy_manifests.py;
- kubelet behaviors (restart policy, resource limits, PVC binding).

Every deviation is recorded in the artifact
(``artifacts/container_run.json``) so "executed in namespaces" can
never be mistaken for "deployed on a cluster".

Usage: sudo-capable shell, from the repo root:
    python deploy/run_containerized.py [--steps N]
"""

from __future__ import annotations

import argparse
import json
import os
import shlex
import shutil
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ROOTFS = "/tmp/slt_container_rootfs"
MANIFEST = os.path.join(REPO, "deploy", "split-learning.yaml")
PORT = 8000

# the Dockerfile's ENV block (deploy/Dockerfile), plus the hygiene pin
# for the host's device-plugin shim which the real base image would not
# even have installed
IMAGE_ENV = {
    "PYTHONPATH": "/app",
    "SLT_NATIVE_CACHE": "/app/native-cache",
    "JAX_PLATFORMS": "cpu",
    "HOME": "/home/appuser",
    "PATH": "/opt/venv/bin:/usr/local/bin:/usr/bin:/bin",
}

HOST_BINDS = ["usr", "bin", "sbin", "lib", "lib64", "etc", "opt", "dev"]


def manifest_containers():
    import yaml
    server_cmd = client_cmd = init_cmd = None
    server_env = client_env = {}
    with open(MANIFEST) as f:
        for doc in yaml.safe_load_all(f):
            if not doc:
                continue
            kind = doc.get("kind")
            spec = (doc.get("spec", {}).get("template", {})
                    .get("spec", {}))
            if kind == "Deployment" and doc["metadata"]["name"] == \
                    "split-server":
                c = spec["containers"][0]
                server_cmd = c["command"]
                server_env = {e["name"]: e.get("value", "")
                              for e in c.get("env", [])}
                probe = c["readinessProbe"]["httpGet"]
                assert probe["path"] == "/health" and probe["port"] == PORT
            if kind == "Job" and doc["metadata"]["name"] == "split-client":
                init_cmd = spec["initContainers"][0]["command"]
                c = spec["containers"][0]
                client_cmd = c["command"]
                client_env = {e["name"]: e.get("value", "")
                              for e in c.get("env", [])}
    assert server_cmd and client_cmd and init_cmd
    return (server_cmd, server_env), (client_cmd, client_env), init_cmd


def build_rootfs() -> None:
    """The Dockerfile runtime stage, executed: COPY + builder-stage
    native precompile + user/dir layout."""
    if os.path.exists(ROOTFS):
        shutil.rmtree(ROOTFS)
    for d in (["app", "proc", "tmp", "home/appuser", "ckpt/server",
               "ckpt/client", "data"] + HOST_BINDS):
        os.makedirs(os.path.join(ROOTFS, d), exist_ok=True)
    # COPY split_learning_tpu/
    shutil.copytree(os.path.join(REPO, "split_learning_tpu"),
                    os.path.join(ROOTFS, "app", "split_learning_tpu"))
    # builder stage: pre-compile the native codec into the image cache
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, '.'); "
         "from split_learning_tpu import native; "
         "assert native.codec.available(), native.codec.build_error()"],
        cwd=REPO, capture_output=True, text=True,
        env=dict(os.environ,
                 SLT_NATIVE_CACHE=os.path.join(ROOTFS, "app",
                                               "native-cache")))
    if out.returncode:
        raise SystemExit("native codec precompile failed: " + out.stderr)
    # USER appuser (uid 1000) owns its writable surfaces
    for d in ("home/appuser", "ckpt", "data", "app/native-cache"):
        subprocess.run(["chown", "-R", "1000:1000",
                        os.path.join(ROOTFS, d)], check=True)


def container_argv(command, extra_env, hostname):
    """unshare(mount|pid|uts) -> bind image mounts -> chroot -> drop to
    uid 1000 -> exec the manifest command with the image ENV."""
    env = dict(IMAGE_ENV)
    env.update(extra_env)
    env_args = " ".join(f"{k}={shlex.quote(str(v))}"
                        for k, v in env.items())
    # host binds remount read-only (top mount; /dev keeps its submounts
    # and stays rw — it needs writable /dev/shm), so the container
    # cannot write through them even where host perms would allow
    binds = "\n".join(
        f"mount --rbind /{d} {ROOTFS}/{d} 2>/dev/null || true"
        + ("" if d == "dev" else
           f"\nmount -o remount,ro,bind {ROOTFS}/{d} 2>/dev/null || true")
        for d in HOST_BINDS)
    script = f"""
set -e
hostname {hostname}
mount -t tmpfs tmpfs {ROOTFS}/tmp
mount -t proc proc {ROOTFS}/proc
{binds}
exec chroot {ROOTFS} /usr/bin/setpriv --reuid 1000 --regid 1000 \
  --clear-groups /usr/bin/env -i {env_args} \
  sh -c 'cd /app && exec "$@"' -- {" ".join(shlex.quote(c) for c in command)}
"""
    return ["unshare", "--mount", "--pid", "--fork", "--uts",
            "sh", "-euc", script]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=6,
                    help="cap the client Job's steps for the evidence "
                         "run (the manifest itself runs a full config)")
    ap.add_argument("--out", default=os.path.join(
        REPO, "artifacts", "container_run.json"))
    args = ap.parse_args()

    if os.geteuid() != 0:
        raise SystemExit("needs root (namespace + chroot)")

    (server_cmd, server_env), (client_cmd, client_env), init_cmd = \
        manifest_containers()

    deviations = [
        "base image python:3.11-slim not pullable (zero egress): host "
        "interpreter/libraries bind-mounted in its place (remounted "
        "read-only except /dev, which keeps rw submounts like "
        "/dev/shm)",
        "k8s Service DNS 'split-server' rewritten to 127.0.0.1; "
        "containers share the host network namespace",
        f"client Job steps capped at {args.steps} for the evidence run",
        "kubelet semantics (restartPolicy, resources, PVC binding) not "
        "executed — schema-tested only (tests/test_deploy_manifests.py)",
    ]
    rewrite = lambda argv: [a.replace("split-server", "127.0.0.1")
                            for a in argv]
    client_cmd = rewrite(client_cmd) + ["--steps", str(args.steps)]
    init_cmd = rewrite(init_cmd)

    print("[container] building rootfs (Dockerfile runtime stage)...",
          file=sys.stderr)
    build_rootfs()

    art = {
        "provenance": {
            "date": time.strftime("%Y-%m-%d"),
            "command": "deploy/run_containerized.py",
            "what": "deploy/split-learning.yaml container commands "
                    "executed in mount+pid+uts namespaces, chrooted "
                    "into the Dockerfile runtime-stage rootfs, as "
                    "uid 1000",
        },
        "deviations": deviations,
        "server_command": server_cmd,
        "client_command": client_cmd,
    }

    # a stale containerized server from a torn-down run would hold the
    # port with a deleted rootfs under it (observed: random_device
    # errors from a /dev that no longer exists) — refuse to start over
    import socket
    with socket.socket() as s:
        if s.connect_ex(("127.0.0.1", PORT)) == 0:
            raise SystemExit(f"port {PORT} already in use — kill the "
                             "stale container first")

    print("[container] starting server container...", file=sys.stderr)
    server_log = open("/tmp/slt_container_server.log", "wb")
    server = subprocess.Popen(container_argv(server_cmd, server_env,
                                             "split-server"),
                              stdout=server_log, stderr=server_log,
                              start_new_session=True)
    try:
        # the Job's init-container readiness barrier, verbatim
        print("[container] init container (readiness barrier)...",
              file=sys.stderr)
        t0 = time.time()
        # bytes, not text: curl prints the binary msgpack health body
        init = subprocess.run(container_argv(init_cmd, {}, "split-client"),
                              capture_output=True, timeout=180)
        art["init_container"] = {"returncode": init.returncode,
                                 "waited_s": round(time.time() - t0, 1)}
        if init.returncode:
            raise SystemExit(
                "init container failed: "
                + init.stderr.decode(errors="replace")[-400:])

        # readinessProbe, from outside the container
        with urllib.request.urlopen(
                f"http://127.0.0.1:{PORT}/health", timeout=10) as r:
            art["readiness_probe"] = {"status": r.status,
                                      "bytes": len(r.read())}

        print("[container] running client Job container...",
              file=sys.stderr)
        t0 = time.time()
        client = subprocess.run(container_argv(client_cmd, client_env,
                                               "split-client"),
                                capture_output=True, timeout=600)
        cout = client.stdout.decode(errors="replace")
        cerr = client.stderr.decode(errors="replace")
        tail = cout.strip().splitlines()[-3:]
        art["client_job"] = {
            "returncode": client.returncode,
            "wall_s": round(time.time() - t0, 1),
            "stdout_tail": tail,
        }
        if client.returncode:
            raise SystemExit("client Job failed: " + (cerr + cout)[-600:])
    finally:
        # TERM the whole session: the namespace wrapper (unshare/sh)
        # does not forward signals to the chroot'd server, which would
        # otherwise outlive this script holding the port
        import signal
        try:
            os.killpg(server.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            server.wait(timeout=20)
        except subprocess.TimeoutExpired:
            os.killpg(server.pid, signal.SIGKILL)
        server_log.close()

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps({"c15_evidence": "namespace-container run ok",
                      "client_rc": art["client_job"]["returncode"],
                      "artifact": args.out}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
