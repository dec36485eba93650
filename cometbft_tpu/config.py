"""Node configuration (reference: config/config.go:93 + config/toml.go).

A TOML file under <home>/config/config.toml, decoded into nested
dataclasses.  Consensus-critical parameters are NOT here — they live
on-chain as ConsensusParams (types/params.py); this file holds only
operator-local knobs, exactly like the reference split.
"""

from __future__ import annotations

import os
import tomllib
from dataclasses import dataclass, field, fields

from .consensus.config import ConsensusConfig

DEFAULT_HOME = os.path.expanduser("~/.cometbft-tpu")


@dataclass
class BaseConfig:
    moniker: str = "node"
    proxy_app: str = "kvstore"  # "kvstore" | "noop" | tcp://addr (socket)
    # "native" = the C++ log-structured engine (native/kvstore.cc, the
    # analogue of the reference's pebble backend); "sqlite" | "memdb"
    db_backend: str = "native"
    block_sync: bool = True
    genesis_file: str = "config/genesis.json"
    priv_validator_key_file: str = "config/priv_validator_key.json"
    priv_validator_state_file: str = "data/priv_validator_state.json"
    # when set (host:port), the node listens here for a remote signer
    # instead of using the file privval (config.go PrivValidatorListenAddr)
    priv_validator_laddr: str = ""
    node_key_file: str = "config/node_key.json"
    log_level: str = "info"
    # snapshot cadence for the built-in kvstore apps (the reference e2e
    # app's snapshot_interval); statesync peers can only serve snapshots
    # taken at these heights
    app_snapshot_interval: int = 100
    tx_index: str = "kv"  # "kv" | "null" | "psql" (config.go TxIndexConfig)
    # for tx_index = "psql": a DB conn string — postgres when psycopg2 is
    # installed, or "sqlite:///path" (indexer/sink.py SQLEventSink)
    psql_conn: str = ""


@dataclass
class P2PConfig:
    laddr: str = "tcp://0.0.0.0:26656"
    external_address: str = ""
    persistent_peers: str = ""  # comma-separated id@host:port
    seeds: str = ""  # comma-separated id@host:port
    pex: bool = True
    seed_mode: bool = False
    addr_book_file: str = "config/addrbook.json"
    max_num_inbound_peers: int = 40
    max_num_outbound_peers: int = 10
    send_rate: int = 5_120_000  # bytes/sec (connection.go:40)
    recv_rate: int = 5_120_000
    handshake_timeout: float = 20.0
    dial_timeout: float = 3.0


@dataclass
class MempoolConfig:
    size: int = 5000
    max_tx_bytes: int = 1024 * 1024
    max_txs_bytes: int = 64 * 1024 * 1024
    cache_size: int = 10000
    keep_invalid_txs_in_cache: bool = False
    recheck: bool = True
    broadcast: bool = True


@dataclass
class StatesyncConfig:
    enable: bool = False
    # comma-separated full-node RPC endpoints the light-client state
    # provider verifies against (config.go StateSyncConfig.RPCServers;
    # first = primary, rest = witnesses)
    rpc_servers: str = ""
    trust_height: int = 0
    trust_hash: str = ""
    trust_period: float = 168 * 3600.0  # seconds
    discovery_time: float = 15.0
    chunk_request_timeout: float = 10.0


@dataclass
class RPCConfig:
    laddr: str = "tcp://127.0.0.1:26657"
    max_open_connections: int = 900
    # enables dial_seeds/dial_peers (reference config.go RPCConfig.Unsafe)
    unsafe: bool = False
    # data-companion services — the reference's grpc_laddr (public
    # block/block-results/version) and grpc_privileged_laddr (pruning
    # retain-height API), served over the varint-proto socket transport
    # (rpc/services.py).  Separate listeners so the pruning API can be
    # firewalled independently of the read-only services.
    companion_laddr: str = ""
    companion_privileged_laddr: str = ""


@dataclass
class InstrumentationConfig:
    prometheus: bool = False
    prometheus_listen_addr: str = ":26660"
    # separate opt-in listener for /debug/threads + /debug/heap — kept
    # off the metrics port so scraping never exposes stack/heap contents
    # (the reference likewise gates pprof behind its own pprof_laddr,
    # config.go pprof_laddr)
    pprof_laddr: str = ""


@dataclass
class Config:
    home: str = DEFAULT_HOME
    base: BaseConfig = field(default_factory=BaseConfig)
    p2p: P2PConfig = field(default_factory=P2PConfig)
    mempool: MempoolConfig = field(default_factory=MempoolConfig)
    consensus: ConsensusConfig = field(default_factory=ConsensusConfig)
    statesync: StatesyncConfig = field(default_factory=StatesyncConfig)
    rpc: RPCConfig = field(default_factory=RPCConfig)
    instrumentation: InstrumentationConfig = field(
        default_factory=InstrumentationConfig
    )

    # ------------------------------------------------------------- paths

    def _abs(self, rel: str) -> str:
        return rel if os.path.isabs(rel) else os.path.join(self.home, rel)

    def genesis_file(self) -> str:
        return self._abs(self.base.genesis_file)

    def node_key_file(self) -> str:
        return self._abs(self.base.node_key_file)

    def priv_validator_key_file(self) -> str:
        return self._abs(self.base.priv_validator_key_file)

    def priv_validator_state_file(self) -> str:
        return self._abs(self.base.priv_validator_state_file)

    def db_dir(self) -> str:
        return self._abs("data")

    def wal_file(self) -> str:
        return self._abs(self.consensus.wal_path)

    def config_file(self) -> str:
        return self._abs("config/config.toml")

    def validate_basic(self) -> None:
        if self.base.db_backend not in ("native", "sqlite", "memdb"):
            raise ValueError(f"unknown db_backend {self.base.db_backend!r}")
        if self.base.tx_index not in ("kv", "null", "psql"):
            raise ValueError(f"unknown tx_index {self.base.tx_index!r}")
        if self.base.tx_index == "psql" and not self.base.psql_conn:
            raise ValueError("tx_index = \"psql\" requires psql_conn")
        if self.statesync.enable and not (
            self.statesync.trust_height > 0 and self.statesync.trust_hash
        ):
            raise ValueError(
                "statesync.enable requires trust_height and trust_hash"
            )


# --------------------------------------------------------------- loading

_SECTIONS = {
    "p2p": P2PConfig,
    "mempool": MempoolConfig,
    "consensus": ConsensusConfig,
    "statesync": StatesyncConfig,
    "rpc": RPCConfig,
    "instrumentation": InstrumentationConfig,
}


def load_config(home: str) -> Config:
    """Read <home>/config/config.toml over the defaults."""
    cfg = Config(home=home)
    path = cfg.config_file()
    if not os.path.exists(path):
        return cfg
    with open(path, "rb") as f:
        data = tomllib.load(f)
    data, _ = _apply_renames(data)  # old configs load with values intact
    _apply(cfg.base, data)  # top-level keys are the base section
    for name, cls in _SECTIONS.items():
        if name in data:
            _apply(getattr(cfg, name), data[name])
    cfg.validate_basic()
    return cfg


def _apply(obj, data: dict) -> None:
    for f in fields(obj):
        if f.name in data:
            setattr(obj, f.name, data[f.name])


# Cross-version key renames (internal/confix/migrations.go's per-version
# plans): "old key" -> "new key", applied before the known/obsolete split
# so an old config carries its VALUES across a rename instead of dropping
# them.  Keys are dotted ("" section = top level); a None target deletes.
# The entries mirror the reference's own history (fast_sync -> block_sync
# and the [fastsync] section in v0.37, config.go).
_RENAMES: dict[str, str | None] = {
    "fast_sync": "block_sync",
    "fastsync.version": None,  # folded into the engine; no knob survives
    "blocksync.version": None,
    # order matters: psql-conn must leave the [tx_index] section BEFORE
    # the indexer key collapses the section into a top-level scalar
    "tx_index.psql-conn": "psql_conn",
    "tx_index.indexer": "tx_index",
}


def _apply_renames(raw: dict) -> tuple[dict, list[str]]:
    """Flatten-rename pass: returns (rewritten raw, renamed-key report)."""
    renamed: list[str] = []
    out: dict = {k: (dict(v) if isinstance(v, dict) else v) for k, v in raw.items()}

    def pop_dotted(key: str):
        if "." in key:
            sec, k = key.split(".", 1)
            if isinstance(out.get(sec), dict) and k in out[sec]:
                v = out[sec].pop(k)
                if not out[sec]:
                    del out[sec]
                return True, v
            return False, None
        if key in out and not isinstance(out[key], dict):
            return True, out.pop(key)
        return False, None

    def set_dotted(key: str, v) -> None:
        if "." in key:
            sec, k = key.split(".", 1)
            out.setdefault(sec, {})[k] = v
            return
        prev = out.get(key)
        if isinstance(prev, dict):
            # a section collapsing into a scalar (old [tx_index] table ->
            # top-level key): surface any leftover keys rather than
            # silently burying them under the new scalar
            renamed.extend(f"{key}.{k} (retired)" for k in prev)
        out[key] = v

    for old, new in _RENAMES.items():
        if old == new:
            continue
        found, v = pop_dotted(old)
        if not found:
            continue
        if new is None:
            renamed.append(f"{old} (retired)")
        else:
            set_dotted(new, v)
            renamed.append(f"{old} -> {new}")
    return out, renamed


def migrate_report(home: str) -> dict:
    """confix-style migration summary (internal/confix): compare the
    on-disk TOML against the current schema and report what a rewrite
    would rename (old keys whose values carry over), add (new keys at
    defaults), drop (obsolete keys), and keep.  Pure analysis — the
    caller decides whether to rewrite."""
    cfg = Config(home=home)
    path = cfg.config_file()
    raw: dict = {}
    if os.path.exists(path):
        with open(path, "rb") as f:
            raw = tomllib.load(f)
    raw, renamed = _apply_renames(raw)

    known: dict[str, set[str]] = {
        "": {f.name for f in fields(cfg.base)},
    }
    for name, _cls in _SECTIONS.items():
        known[name] = {f.name for f in fields(getattr(cfg, name))}

    kept: list[str] = []
    dropped: list[str] = []
    present: dict[str, set[str]] = {"": set()}
    for key, val in raw.items():
        if isinstance(val, dict):
            present[key] = set(val)
            if key not in known:
                dropped.extend(f"{key}.{k}" for k in val)
                continue
            for k in val:
                (kept if k in known[key] else dropped).append(f"{key}.{k}")
        else:
            present[""].add(key)
            (kept if key in known[""] else dropped).append(key)

    added = []
    for section, names in known.items():
        have = present.get(section, set())
        for k in sorted(names - have):
            added.append(f"{section}.{k}" if section else k)
    return {
        "added": added,
        "dropped": sorted(dropped),
        "kept": sorted(kept),
        "renamed": renamed,
    }


def save_config(cfg: Config) -> None:
    """Write the TOML template with current values (config/toml.go)."""
    path = cfg.config_file()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    out = ["# CometBFT-TPU node configuration", ""]
    out.extend(_emit(cfg.base))
    for name in _SECTIONS:
        out.append("")
        out.append(f"[{name}]")
        out.extend(_emit(getattr(cfg, name)))
    with open(path, "w") as f:
        f.write("\n".join(out) + "\n")


def _emit(obj) -> list[str]:
    lines = []
    for f in fields(obj):
        v = getattr(obj, f.name)
        if isinstance(v, bool):
            tv = "true" if v else "false"
        elif isinstance(v, (int, float)):
            tv = repr(v)
        else:
            tv = '"' + str(v).replace("\\", "\\\\").replace('"', '\\"') + '"'
        lines.append(f"{f.name} = {tv}")
    return lines
