"""Launcher unit tests (pure, mock-level) — parity with the reference's
``test/test_run.py``: arg parsing, host parsing, allocation, config-file
precedence, env synthesis."""

import os
import textwrap

import pytest

from horovod_tpu.run import parse_args, check_build
from horovod_tpu.run import config_parser, launcher


def test_parse_hosts():
    assert launcher.parse_hosts("a:2,b:4") == [("a", 2), ("b", 4)]
    assert launcher.parse_hosts("localhost") == [("localhost", 1)]


def test_parse_hostfile(tmp_path):
    p = tmp_path / "hosts"
    p.write_text(
        textwrap.dedent(
            """
            # comment
            nodeA slots=2
            nodeB slots=4  # trailing
            nodeC
            """
        )
    )
    assert launcher.parse_hostfile(str(p)) == [
        ("nodeA", 2), ("nodeB", 4), ("nodeC", 1)
    ]


def test_allocate_two_hosts():
    slots = launcher.allocate([("a", 2), ("b", 2)], 4)
    assert [s.rank for s in slots] == [0, 1, 2, 3]
    assert [s.hostname for s in slots] == ["a", "a", "b", "b"]
    assert [s.local_rank for s in slots] == [0, 1, 0, 1]
    assert all(s.local_size == 2 for s in slots)
    assert [s.cross_rank for s in slots] == [0, 0, 1, 1]
    assert all(s.cross_size == 2 for s in slots)


def test_allocate_insufficient_slots():
    with pytest.raises(ValueError):
        launcher.allocate([("a", 1)], 3)


def test_parse_args_knobs():
    args = parse_args(
        [
            "-np", "4", "-H", "localhost:4", "--fusion-threshold-mb", "32",
            "--cycle-time-ms", "3.5", "--autotune", "--timeline-filename",
            "/tmp/tl.json", "python", "train.py",
        ]
    )
    assert args.num_proc == 4
    assert args.fusion_threshold_mb == 32
    assert args.cycle_time_ms == 3.5
    assert args.autotune is True
    assert args.command == ["python", "train.py"]


def test_set_env_from_args():
    args = parse_args(
        ["-np", "2", "--fusion-threshold-mb", "32", "--cycle-time-ms", "2",
         "--log-level", "debug", "x"]
    )
    env = config_parser.set_env_from_args({}, args)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
    assert env["HOROVOD_CYCLE_TIME"] == "2.0"
    assert env["HOROVOD_LOG_LEVEL"] == "debug"


def test_config_file_with_cli_override(tmp_path):
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(
        textwrap.dedent(
            """
            fusion:
              threshold-mb: 16
              cycle-time-ms: 7.5
            autotune:
              enabled: true
            timeline:
              filename: /tmp/from_yaml.json
            """
        )
    )
    # CLI sets cycle-time explicitly: must beat YAML; others come from YAML.
    args = parse_args(
        ["-np", "2", "--config-file", str(cfg), "--cycle-time-ms", "2.0", "x"]
    )
    assert args.cycle_time_ms == 2.0
    assert args.fusion_threshold_mb == 16
    assert args.autotune is True
    assert args.timeline_filename == "/tmp/from_yaml.json"


def test_check_build_output():
    out = check_build()
    assert "[X] JAX" in out
    assert "XLA" in out
    assert "[ ] MPI" in out


def test_build_rank_env():
    slot = launcher.SlotInfo("localhost", 1, 4, 1, 2, 0, 2)
    env = launcher.build_rank_env(slot, {"PATH": "/bin"}, "127.0.0.1", 9999,
                                  "127.0.0.1:8888")
    assert env["HOROVOD_RANK"] == "1"
    assert env["HOROVOD_SIZE"] == "4"
    assert env["HOROVOD_LOCAL_RANK"] == "1"
    assert env["HOROVOD_LOCAL_SIZE"] == "2"
    assert env["HOROVOD_CONTROLLER_ADDR"] == "127.0.0.1"
    assert env["HOROVOD_CONTROLLER_PORT"] == "9999"
    assert env["HOROVOD_JAX_COORDINATOR"] == "127.0.0.1:8888"
    assert env["PATH"] == "/bin"


def test_tpu_pod_allocation(monkeypatch):
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "w0,w1,w2,w3")
    slots = launcher.tpu_pod_allocation()
    assert len(slots) == 4
    assert [s.hostname for s in slots] == ["w0", "w1", "w2", "w3"]
    assert all(s.local_size == 1 for s in slots)
    assert [s.cross_rank for s in slots] == [0, 1, 2, 3]


def test_kv_store_roundtrip():
    from horovod_tpu.run.http_server import KVStoreClient, KVStoreServer

    server = KVStoreServer()
    port = server.start()
    try:
        client = KVStoreClient("127.0.0.1", port)
        client.put("global", "k1", b"hello")
        assert client.get("global", "k1") == b"hello"
        assert client.get("global", "missing") is None
        assert client.wait("global", "k1") == b"hello"
    finally:
        server.stop()


def test_disable_cache_and_start_timeout_flags():
    from horovod_tpu.run.run import parse_args

    args = parse_args(["-np", "2", "--disable-cache",
                       "--start-timeout", "45", "python", "x.py"])
    assert args.disable_cache is True
    assert args.start_timeout == 45


def test_ssh_preflight_unreachable_fails_fast(monkeypatch, tmp_path):
    """Reference run/run.py:62-115 parity: a dead host yields one clear
    per-host error before any rank launches; ssh is mocked."""
    import subprocess

    from horovod_tpu.run import launcher
    from horovod_tpu.run.disk_cache import DiskCache

    calls = []

    def fake_run(cmd, **kw):
        calls.append(cmd)
        host = cmd[-2]

        class R:
            returncode = 0 if host == "good-host" else 255

        return R()

    monkeypatch.setattr(subprocess, "run", fake_run)
    cache = DiskCache(str(tmp_path / "c.json"), ttl_seconds=300)
    with pytest.raises(RuntimeError) as e:
        launcher.check_hosts_reachable(
            ["good-host", "bad-host", "localhost"], cache=cache
        )
    assert "bad-host" in str(e.value)
    assert "good-host" not in str(e.value)
    # localhost is never probed.
    assert all("localhost" not in c for c in calls)


def test_ssh_preflight_caches_successes(monkeypatch, tmp_path):
    import subprocess

    from horovod_tpu.run import launcher
    from horovod_tpu.run.disk_cache import DiskCache

    calls = []

    def fake_run(cmd, **kw):
        calls.append(cmd)

        class R:
            returncode = 0

        return R()

    monkeypatch.setattr(subprocess, "run", fake_run)
    cache = DiskCache(str(tmp_path / "c.json"), ttl_seconds=300)
    launcher.check_hosts_reachable(["h1", "h2"], cache=cache)
    assert len(calls) == 2
    # Second launch: cache hits, no ssh spawned.
    launcher.check_hosts_reachable(["h1", "h2"], cache=cache)
    assert len(calls) == 2
    # Expired TTL re-probes.
    expired = DiskCache(str(tmp_path / "c.json"), ttl_seconds=0)
    launcher.check_hosts_reachable(["h1"], cache=expired)
    assert len(calls) == 3


def test_ssh_preflight_failure_not_cached(monkeypatch, tmp_path):
    import subprocess

    from horovod_tpu.run import launcher
    from horovod_tpu.run.disk_cache import DiskCache

    rc = {"v": 255}
    calls = []

    def fake_run(cmd, **kw):
        calls.append(cmd)

        class R:
            returncode = rc["v"]

        return R()

    monkeypatch.setattr(subprocess, "run", fake_run)
    cache = DiskCache(str(tmp_path / "c.json"), ttl_seconds=300)
    with pytest.raises(RuntimeError):
        launcher.check_hosts_reachable(["flaky"], cache=cache)
    # Host fixed: must re-probe (failures are never cached) and pass.
    rc["v"] = 0
    launcher.check_hosts_reachable(["flaky"], cache=cache)
    assert len(calls) == 2


def test_ssh_fanout_end_to_end_via_shim(tmp_path):
    """Two-'host' end-to-end through the REAL ssh fan-out (the
    ssh path + ring NIC probe had only unit/mock coverage). A PATH
    shim stands in for the ssh binary — it consumes the option prefix and
    execs the remote command string locally — so every production layer
    runs for real: hostfile parsing, the BatchMode pre-flight, the
    HMAC-authed ring NIC probe over 'hosta'/'hostb' (whose probed
    127.0.0.1 answer is the ONLY reason the unresolvable fake hostnames
    can rendezvous — exercising HOROVOD_PROBED_CONTROLLER_ADDR for
    real), build_remote_command's cd+env-prefix quoting, and the fan-out
    kill/collect loop. For real two-container coverage see
    docker-compose.ssh.yml + tools/ssh_e2e_compose.sh."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shim_dir = tmp_path / "bin"
    shim_dir.mkdir()
    shim = shim_dir / "ssh"
    shim.write_text(textwrap.dedent("""\
        #!/bin/sh
        # Fake ssh: swallow options, record the target host, run locally.
        while [ $# -gt 0 ]; do
          case "$1" in
            -o|-p) shift 2 ;;
            -*) shift ;;
            *) break ;;
          esac
        done
        host="$1"; shift
        echo "$host" >> "$SSH_SHIM_LOG"
        exec /bin/sh -c "$*"
        """))
    shim.chmod(0o755)

    worker = tmp_path / "worker.py"
    worker.write_text(textwrap.dedent("""\
        import os
        import jax
        jax.config.update('jax_platforms', 'cpu')
        import numpy as np
        import horovod_tpu as hvd
        hvd.init()
        import jax.numpy as jnp
        s = hvd.allreduce(jnp.full((2,), float(hvd.rank() + 1)),
                          op=hvd.Sum, name='e2e')
        print('SSHE2E', hvd.rank(), hvd.size(), float(np.asarray(s)[0]),
              flush=True)
        hvd.shutdown()
        """))

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PATH"] = f"{shim_dir}{os.pathsep}" + env.get("PATH", "")
    env["SSH_SHIM_LOG"] = str(tmp_path / "ssh_calls.log")
    env["PYTHONPATH"] = os.pathsep.join(
        [repo, env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    out_dir = tmp_path / "out"
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
         "-H", "hosta:1,hostb:1", "--disable-cache",
         "--output-dir", str(out_dir), sys.executable, str(worker)],
        env=env, cwd=repo, capture_output=True, timeout=240, text=True,
    )
    outs = {}
    for fn in os.listdir(out_dir):
        outs[fn] = (out_dir / fn).read_text()
    assert proc.returncode == 0, (proc.stdout, proc.stderr, outs)
    lines = sorted(
        l for o in outs.values() for l in o.splitlines()
        if l.startswith("SSHE2E")
    )
    # Sum over ranks: 1.0 + 2.0 = 3.0 on both.
    assert lines == ["SSHE2E 0 2 3.0", "SSHE2E 1 2 3.0"], (lines, outs)
    # Both fake hosts went through the ssh binary (pre-flight + probe +
    # fan-out), not through any local-spawn shortcut.
    ssh_hosts = set(
        (tmp_path / "ssh_calls.log").read_text().split()
    )
    assert {"hosta", "hostb"} <= ssh_hosts, ssh_hosts
