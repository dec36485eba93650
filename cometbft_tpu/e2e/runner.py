"""E2E testnet runner: multi-process localnets with perturbations
(reference: test/e2e/runner — setup/start/load/perturb/wait/test stages
over docker-compose; here the nodes are OS processes driven through the
CLI, which exercises the same real binaries + sockets without docker).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request
from dataclasses import dataclass, field

from ..cli import main as cli_main
from ..config import load_config, save_config
from ..utils import compilecache
from ..utils.log import get_logger

_log = get_logger("e2e.runner")


@dataclass
class NodeSpec:
    """One manifest entry (test/e2e/pkg/manifest.go)."""

    name: str
    start_at: int = 0  # height to join at (0 = genesis)
    # kill|pause|restart|disconnect|wedge|double_sign (disconnect =
    # network partition via SIGUSR1 toggle, the runner/perturb.go
    # docker-disconnect analogue; wedge/double_sign arm the fault
    # registry over RPC — utils/fail.py — and require the node to run
    # with COMETBFT_TPU_FAULT_RPC=1 in its env)
    perturbations: list[str] = field(default_factory=list)
    # extra environment for the node process (chaos scenarios set
    # COMETBFT_TPU_FAULT_RPC / COMETBFT_TPU_HEALTH / failover knobs here)
    env: dict[str, str] = field(default_factory=dict)
    # verify-plane tenant this chain's node claims
    # (COMETBFT_TPU_VERIFYSVC_TENANT): how process-level chains share a
    # multi-tenant verify plane; "" keeps the default tenant
    tenant: str = ""
    # per-node validator key type ("" = the manifest-wide key_type).
    # A mix of key types across nodes produces a MIXED validator set in
    # genesis (e.g. ed25519 + bls12_381): commit verification then takes
    # the sequential fallback (types/validation.should_batch_verify
    # requires a homogeneous set), and the genesis/proto encode paths
    # must round-trip every key type (crypto/encoding)
    key_type: str = ""
    # per-link shaping (runner/latency_emulation.go analogue): outbound
    # delay +- jitter applied at this node's sockets (utils/netutil)
    latency_ms: float = 0.0
    latency_jitter_ms: float = 0.0
    # generator axes (generator/generate.go): ABCI transport and DB
    # backend; "" = the config default
    abci: str = "local"  # "local" | "socket" | "grpc" (external app)
    db_backend: str = ""  # "" | "native" | "sqlite" | "memdb"
    # join mid-run via statesync (requires start_at > 0): the runner
    # fetches trust height/hash from a running node right before launch
    # (manifest.go StateSync)
    state_sync: bool = False


@dataclass
class Manifest:
    chain_id: str = "e2e-chain"
    nodes: list[NodeSpec] = field(default_factory=list)
    load_tx_per_round: int = 5
    target_height: int = 12
    # validator key type for the whole net (generate.go's keyType axis);
    # non-ed25519 nets exercise the sequential verify fallback
    key_type: str = "ed25519"


class E2ENode:
    def __init__(self, name: str, home: str, rpc_port: int,
                 latency_ms: float = 0.0, latency_jitter_ms: float = 0.0,
                 abci_port: int = 0, abci_scheme: str = "tcp",
                 extra_env: dict[str, str] | None = None):
        self.name = name
        self.home = home
        self.rpc_port = rpc_port
        self.latency_ms = latency_ms
        self.latency_jitter_ms = latency_jitter_ms
        self.abci_port = abci_port  # non-zero: external app process
        self.abci_scheme = abci_scheme  # "tcp" (socket) | "grpc"
        self.extra_env = dict(extra_env or {})
        self.proc: subprocess.Popen | None = None
        self.app_proc: subprocess.Popen | None = None

    def start(self) -> None:
        env = dict(os.environ)
        # e2e nodes run on the CPU backend: a chip belongs to one process
        # at a time, so N node processes on one host cannot share it, and
        # the harness process may hold it itself.  The same one-writer
        # caution places their compile cache: kill/restart perturbations
        # SIGKILL nodes mid-write (utils/compilecache.HARNESS_DIR).
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault(compilecache.ENV_VAR, compilecache.HARNESS_DIR)
        # the test conftest forces the device threshold to 1 so kernel
        # tests exercise the device paths; a NODE inheriting that would
        # compile an XLA program to verify a 2-signature commit — scrub
        # back to the production default (host path at localnet scale)
        env.pop("COMETBFT_TPU_DEVICE_BATCH_MIN", None)
        if self.latency_ms or self.latency_jitter_ms:
            env["COMETBFT_TPU_TEST_LATENCY_MS"] = (
                f"{self.latency_ms}:{self.latency_jitter_ms}"
            )
        env.update(self.extra_env)
        from ..utils import tracing as _tracing

        _tv = env.get("COMETBFT_TPU_TRACE", "").lower()
        _tv_explicit_path = (
            "COMETBFT_TPU_TRACE" in self.extra_env
            and (os.sep in _tv or _tv.endswith(".json"))
        )
        if _tv not in _tracing._OFF_VALUES and not _tv_explicit_path:
            # tracing armed (parent env or node spec): every node exports
            # its OWN trace file at exit — a shared inherited path would
            # be torn by concurrent atexit writers; the chaos/soak
            # epilogues merge the per-process exports into one timeline
            # (utils/tracemerge).  Only an explicit per-node path in the
            # spec's env is left alone.
            env["COMETBFT_TPU_TRACE"] = os.path.join(self.home, "trace.json")
        if self.abci_port and self.app_proc is None:
            # external app rides the ABCI socket or gRPC transport (the
            # generator's abci axis); it outlives node restarts the way
            # the reference's app container does
            self.app_proc = subprocess.Popen(
                [
                    sys.executable, "-m", "cometbft_tpu", "kvstore",
                    "--addr", f"{self.abci_scheme}://127.0.0.1:{self.abci_port}",
                    "--snapshot-interval", "2",
                ],
                env=env,
                stdout=open(os.path.join(self.home, "app.log"), "ab"),
                stderr=subprocess.STDOUT,
            )
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m", "cometbft_tpu",
                "--home", self.home, "start",
                "--rpc-laddr", f"tcp://127.0.0.1:{self.rpc_port}",
            ],
            env=env,
            stdout=open(os.path.join(self.home, "node.log"), "ab"),
            stderr=subprocess.STDOUT,
        )

    def rpc(self, method: str, **params):
        payload = json.dumps(
            {"jsonrpc": "2.0", "id": 1, "method": method, "params": params}
        ).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.rpc_port}",
            data=payload,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            out = json.loads(resp.read())
        if "error" in out:
            raise RuntimeError(out["error"])
        return out["result"]

    def height(self) -> int:
        return int(self.rpc("status")["sync_info"]["latest_block_height"])

    def wait_ready(self, timeout: float = 30.0) -> bool:
        """Poll /tpu_health until the node answers AND is not wedged —
        the readiness wait that replaces bare fixed sleeps wherever the
        runner holds a node handle.  The route answers even with the
        sentinel off (`{"enabled": false}`), so on a plain node this
        degrades to 'the RPC listener is up', which is exactly the old
        sleep's (unchecked) assumption."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc is None or self.proc.poll() is not None:
                return False  # process gone: readiness can never arrive
            try:
                h = self.rpc("tpu_health")
            except Exception as e:  # noqa: BLE001 — RPC not up yet, keep polling
                _log.debug(f"tpu_health poll of {self.name}: {e!r}")
                time.sleep(0.25)
                continue
            if not h.get("enabled", False) or h.get("ready", True):
                return True
            time.sleep(0.25)
        return False

    def arm_fault(self, name: str, value: float = 1.0) -> dict:
        """Arm a fault in the running node via the fault registry's RPC
        endpoint (utils/fail.py; needs COMETBFT_TPU_FAULT_RPC=1 in the
        node's env — NodeSpec.env)."""
        return self.rpc("arm_fault", name=name, value=value)

    def clear_fault(self, name: str | None = None) -> dict:
        return self.rpc("clear_fault", **({"name": name} if name else {}))

    def verify_svc(self) -> dict:
        return self.rpc("verify_svc_status")

    def kill(self) -> None:
        """kill -9: the crash-recovery perturbation (runner/perturb.go)."""
        if self.proc:
            self.proc.kill()
            self.proc.wait(timeout=20)
            self.proc = None

    def pause(self) -> None:
        if self.proc:
            self.proc.send_signal(signal.SIGSTOP)

    def resume(self) -> None:
        if self.proc:
            self.proc.send_signal(signal.SIGCONT)

    def partition_toggle(self) -> None:
        """SIGUSR1: toggle severing the node's p2p sockets (cli.py
        cmd_start's hook)."""
        if self.proc:
            self.proc.send_signal(signal.SIGUSR1)

    def terminate(self) -> None:
        if self.proc:
            try:
                self.proc.terminate()
                self.proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                self.proc.kill()
            self.proc = None
        if self.app_proc:
            try:
                self.app_proc.terminate()
                self.app_proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.app_proc.kill()
            self.app_proc = None


class Runner:
    """setup → start → load → perturb → wait → test
    (test/e2e/runner/main.go stages)."""

    def __init__(self, manifest: Manifest, out_dir: str, base_port: int = 28000):
        self.m = manifest
        self.out = out_dir
        self.base_port = base_port
        self.nodes: list[E2ENode] = []

    # ------------------------------------------------------------- stages

    def setup(self) -> None:
        n = len(self.m.nodes)
        assert cli_main(
            [
                "testnet", "--v", str(n), "--o", self.out,
                "--chain-id", self.m.chain_id,
                "--starting-port", str(self.base_port),
                "--key-type", self.m.key_type,
            ]
        ) == 0
        if any(spec.key_type for spec in self.m.nodes):
            self._apply_node_key_types()
        for i, spec in enumerate(self.m.nodes):
            home = os.path.join(self.out, f"node{i}")
            cfg = load_config(home)
            cfg.consensus.timeout_propose = 1.0
            cfg.consensus.timeout_propose_delta = 0.3
            cfg.consensus.timeout_prevote = 0.5
            cfg.consensus.timeout_prevote_delta = 0.3
            cfg.consensus.timeout_precommit = 0.5
            cfg.consensus.timeout_precommit_delta = 0.3
            # thread-dump endpoint: when a node wedges mid-testnet the
            # runner (and a human) can pull /debug/threads (perturb.go's
            # cometbft debug equivalent)
            cfg.instrumentation.pprof_laddr = (
                f"127.0.0.1:{self.base_port + 2000 + i}"
            )
            # frequent snapshots so a statesync joiner always finds one
            # (the reference e2e app config sets snapshot_interval=3 the
            # same way)
            cfg.base.app_snapshot_interval = 2
            abci_port = 0
            if spec.abci == "socket":
                abci_port = self.base_port + 3000 + i
                cfg.base.proxy_app = f"tcp://127.0.0.1:{abci_port}"
            elif spec.abci == "grpc":
                abci_port = self.base_port + 3000 + i
                cfg.base.proxy_app = f"grpc://127.0.0.1:{abci_port}"
            if spec.db_backend:
                cfg.base.db_backend = spec.db_backend
            save_config(cfg)
            if spec.tenant:
                spec.env.setdefault(
                    "COMETBFT_TPU_VERIFYSVC_TENANT", spec.tenant
                )
            self.nodes.append(
                E2ENode(
                    spec.name,
                    home,
                    self.base_port + 1000 + i,
                    latency_ms=spec.latency_ms,
                    latency_jitter_ms=spec.latency_jitter_ms,
                    abci_port=abci_port,
                    abci_scheme="grpc" if spec.abci == "grpc" else "tcp",
                    extra_env=spec.env,
                )
            )

    def _apply_node_key_types(self) -> None:
        """Regenerate the privval key of every node with a per-spec
        ``key_type`` override and rewrite the SHARED genesis (validator
        list + ConsensusParams.validator.pub_key_types) across all
        homes — a mixed-key-type validator set must round-trip through
        the same genesis.json every node loads."""
        from ..privval.file_pv import FilePV
        from ..types.genesis import GenesisDoc, GenesisValidator

        cfgs = [
            load_config(os.path.join(self.out, f"node{i}"))
            for i in range(len(self.m.nodes))
        ]
        pvs = []
        for cfg, spec in zip(cfgs, self.m.nodes):
            if spec.key_type and spec.key_type != self.m.key_type:
                os.remove(cfg.priv_validator_key_file())
                # the last-sign state belongs to the deleted key: a new
                # key inheriting old height/round/signbytes would trip
                # (or wrongly pass) the double-sign guard
                if os.path.exists(cfg.priv_validator_state_file()):
                    os.remove(cfg.priv_validator_state_file())
                pv = FilePV.load_or_generate(
                    cfg.priv_validator_key_file(),
                    cfg.priv_validator_state_file(),
                    key_type=spec.key_type,
                )
            else:
                pv = FilePV.load_or_generate(
                    cfg.priv_validator_key_file(),
                    cfg.priv_validator_state_file(),
                )
            pvs.append(pv)
        with open(cfgs[0].genesis_file()) as f:
            doc = GenesisDoc.from_json(f.read())
        doc.validators = [
            GenesisValidator(
                pub_key_type=pv.key.pub_key.type,
                pub_key_bytes=pv.key.pub_key.bytes(),
                power=10,
            )
            for pv in pvs
        ]
        doc.consensus_params.validator.pub_key_types = sorted(
            {pv.key.pub_key.type for pv in pvs}
        )
        for cfg in cfgs:
            doc.save_as(cfg.genesis_file())

    def start(self) -> None:
        for node, spec in zip(self.nodes, self.m.nodes):
            if spec.start_at == 0:
                node.start()
        # readiness, not a fixed grace sleep: the first load round used
        # to race the RPC listeners coming up
        for node, spec in zip(self.nodes, self.m.nodes):
            if spec.start_at == 0 and not node.wait_ready():
                _log.warning(f"{node.name} not ready after start")

    def start_late_nodes(self) -> None:
        started_heights = self._heights(only_running=True)
        tip = max(started_heights) if started_heights else 0
        for node, spec in zip(self.nodes, self.m.nodes):
            if spec.start_at > 0 and node.proc is None and tip >= spec.start_at:
                if spec.state_sync:
                    try:
                        self._configure_statesync(node, spec)
                    except Exception as e:  # noqa: BLE001 — retried next round
                        # usually just "trust root not available yet", but a
                        # persistent failure (config write error) must be
                        # findable, not an eternally silent non-start
                        _log.debug(
                            f"statesync config for {node.name} not ready, "
                            f"will retry: {e!r}"
                        )
                        continue
                node.start()

    def _configure_statesync(self, node: E2ENode, spec: NodeSpec) -> None:
        """Write the joiner's trust root + rpc_servers right before
        launch (runner/setup.go does this from the seed node's /commit —
        the trust hash can only exist once the chain is running)."""
        running = [n for n in self.nodes if n.proc is not None and n is not node]
        if len(running) < 1:
            raise RuntimeError("no running nodes to trust")
        trust_h = max(1, spec.start_at - 2)
        cm = running[0].rpc("commit", height=trust_h)
        trust_hash = cm["signed_header"]["commit"]["block_id"]["hash"]
        cfg = load_config(node.home)
        cfg.statesync.enable = True
        cfg.statesync.trust_height = trust_h
        cfg.statesync.trust_hash = trust_hash
        cfg.statesync.discovery_time = 2.0  # localnet: peers are right there
        cfg.statesync.rpc_servers = ",".join(
            f"127.0.0.1:{n.rpc_port}" for n in running[:2]
        )
        save_config(cfg)

    def load(self, round_id: int) -> None:
        """Submit txs through a random running node (runner/load.go)."""
        for node in self.nodes:
            if node.proc is None:
                continue
            failed = 0
            last_err: Exception | None = None
            for j in range(self.m.load_tx_per_round):
                tx = f"load-{round_id}-{j}={node.name}".encode()
                try:
                    import base64

                    node.rpc("broadcast_tx_sync", tx=base64.b64encode(tx).decode())
                except Exception as e:  # noqa: BLE001 — load-gen rides out node restarts
                    failed += 1
                    last_err = e
            if failed:
                _log.warning(
                    f"load round {round_id} via {node.name}: {failed}/"
                    f"{self.m.load_tx_per_round} submissions failed "
                    f"(last: {last_err!r})"
                )
            break

    def perturb(self) -> None:
        """Apply each node's scripted perturbations (runner/perturb.go)."""
        for node, spec in zip(self.nodes, self.m.nodes):
            for p in spec.perturbations:
                if node.proc is None:
                    continue
                if p == "kill":
                    node.kill()
                    time.sleep(1.0)  # downtime under test, not readiness
                    node.start()
                    if not node.wait_ready():
                        _log.warning(
                            f"{node.name} not ready after kill+restart"
                        )
                elif p == "pause":
                    node.pause()
                    time.sleep(3.0)
                    node.resume()
                elif p == "restart":
                    node.terminate()
                    time.sleep(0.5)  # downtime under test, not readiness
                    node.start()
                    if not node.wait_ready():
                        _log.warning(f"{node.name} not ready after restart")
                elif p == "disconnect":
                    # network partition: sever sockets, not processes
                    # (runner/perturb.go:47-60); heal after a few seconds
                    node.partition_toggle()
                    time.sleep(4.0)
                    node.partition_toggle()
                elif p == "wedge":
                    # inject a device wedge via the fault registry's RPC
                    # arm endpoint: the verify plane must trip to CPU
                    # fallback and keep the node committing, then
                    # restore via probation once healed
                    try:
                        node.arm_fault("wedge_device")
                        time.sleep(6.0)  # wedged window under test
                        node.clear_fault("wedge_device")
                    except Exception as e:  # noqa: BLE001 — fault RPC may be disabled
                        _log.warning(
                            f"wedge perturbation of {node.name} failed "
                            f"(is COMETBFT_TPU_FAULT_RPC=1 set?): {e!r}"
                        )
                elif p == "double_sign":
                    # one byzantine equivocation: the next signed
                    # non-nil prevote is accompanied by a conflicting
                    # broadcast, feeding the evidence pool
                    try:
                        node.arm_fault("double_sign", 1)
                    except Exception as e:  # noqa: BLE001 — fault RPC may be disabled
                        _log.warning(
                            f"double_sign perturbation of {node.name} "
                            f"failed (is COMETBFT_TPU_FAULT_RPC=1 set?): {e!r}"
                        )

    def wait_for_height(self, h: int, timeout: float = 240.0) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self.start_late_nodes()
            hs = self._heights(only_running=True)
            if hs and min(hs) >= h and len(hs) == sum(
                1 for n in self.nodes if n.proc is not None
            ):
                if all(n.proc is not None for n in self.nodes):
                    return True
            time.sleep(1.0)
        return False

    # ------------------------------------------------------------- checks

    def check_invariants(self, upto: int) -> list[str]:
        """Black-box invariants over RPC (test/e2e/tests/*_test.go):
        identical blocks, app hashes, and validator sets everywhere."""
        problems = []
        hashes: dict[int, set[str]] = {}
        for node in self.nodes:
            if node.proc is None:
                continue
            try:
                base = int(
                    node.rpc("status")["sync_info"]["earliest_block_height"]
                )
                for h in range(max(base, 1), upto + 1):
                    b = node.rpc("block", height=h)
                    hashes.setdefault(h, set()).add(b["block_id"]["hash"])
            except Exception as e:  # noqa: BLE001
                problems.append(f"{node.name}: rpc failed: {e}")
        for h, hs in hashes.items():
            if len(hs) > 1:
                problems.append(f"fork at height {h}: {hs}")
        apps = set()
        for node in self.nodes:
            if node.proc is None:
                continue
            try:
                apps.add(node.rpc("status")["sync_info"]["latest_app_hash"])
            except Exception as e:  # noqa: BLE001 — probing possibly-dead nodes
                _log.debug(f"status probe of {node.name} failed: {e!r}")
        # nodes may be at different heights; only flag if everyone reports
        # the same height but different app hashes
        heights = set(self._heights(only_running=True))
        if len(heights) == 1 and len(apps) > 1:
            problems.append(f"app hash divergence at height {heights}: {apps}")
        return problems

    def check_watchdog_fires(self) -> list[str]:
        """A consensus-watchdog re-kick in any node means a scheduled
        timeout evaporated — a state-machine bug the watchdog papered
        over.  The reference runs with no watchdog at all
        (internal/consensus/state.go:795-884), so perturbed runs must
        show zero fires to claim parity."""
        from ..consensus.state import ConsensusState

        token = ConsensusState.WATCHDOG_LOG_TOKEN.encode()
        problems = []
        for node in self.nodes:
            log = os.path.join(node.home, "node.log")
            try:
                with open(log, "rb") as f:
                    for line in f:
                        if token in line:
                            problems.append(
                                f"{node.name}: {line.decode(errors='replace').strip()}"
                            )
            except OSError as e:
                # a node that ran but left no log can't be checked — that
                # is a finding, not a vacuous pass
                problems.append(f"{node.name}: node.log unreadable: {e}")
        return problems

    def dump_stalled(self, target_height: int) -> None:
        """Print /debug/threads of every node behind target — turns a
        CI stall into an actionable trace (debug kill's goroutine dump)."""
        for i, node in enumerate(self.nodes):
            if node.proc is None:
                print(f"[dump] {node.name}: not running")
                continue
            try:
                h = node.height()
            except Exception as e:  # noqa: BLE001
                print(f"[dump] {node.name}: rpc dead: {e}")
                h = -1
            if h >= target_height:
                continue
            try:
                url = f"http://127.0.0.1:{self.base_port + 2000 + i}/debug/threads"
                with urllib.request.urlopen(url, timeout=5) as f:
                    print(f"[dump] {node.name} stalled at {h}:\n{f.read().decode()}")
            except Exception as e:  # noqa: BLE001
                print(f"[dump] {node.name}: pprof unreachable: {e}")

    def stop_all(self) -> None:
        for node in self.nodes:
            node.terminate()

    def _heights(self, only_running: bool = False) -> list[int]:
        out = []
        for node in self.nodes:
            if only_running and node.proc is None:
                continue
            try:
                out.append(node.height())
            except Exception as e:  # noqa: BLE001 — probing possibly-dead nodes
                _log.debug(f"height probe of {node.name} failed: {e!r}")
        return out
