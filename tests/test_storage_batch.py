"""``SQLiteStore.apply_batch`` (hooks/storage.py, ADR 014): a group
commit in as few statements as the batch's order allows must leave
what the same ops leave when they are applied one by one, in one
transaction that a failure or a kill takes back whole, whatever a
record holds; and the counter that says how many statements a commit
took."""

import random
import sqlite3
import sys

import pytest
from test_wire_templates import _pub

from maxmq_tpu import faults
from maxmq_tpu.hooks import storage
from maxmq_tpu.hooks.faultstore import FaultInjectingStore
from maxmq_tpu.hooks.journal import WriteBehindStore
from maxmq_tpu.hooks.storage import SQLiteStore, _spliced_record
from maxmq_tpu.metrics import Registry, _register_storage_metrics

BUCKETS = ("inflight", "retained", "subscriptions")


@pytest.fixture(autouse=True)
def clean_faults():
    faults.clear()
    kill_fn = faults.REGISTRY.kill_fn
    yield
    faults.clear()
    faults.REGISTRY.kill_fn = kill_fn


def random_ops(seed: int, n: int) -> list[tuple]:
    """Ops over a few buckets and a key space small enough that a key
    repeats within a run of one kind, across runs and across prefix
    deletes; the kinds come in runs of seeded length, as the journal's
    do, and one by one."""
    rng = random.Random(seed)
    ops: list[tuple] = []
    while len(ops) < n:
        kind = rng.choice(("put", "put", "delete", "delete",
                           "delete_prefix"))
        run = 1 if kind == "delete_prefix" else rng.choice((1, 2, 7, 300))
        for _ in range(min(run, n - len(ops))):
            bucket = rng.choice(BUCKETS)
            key = f"c{rng.randrange(12)}|{rng.randrange(40)}"
            if kind == "put":
                ops.append((kind, bucket, key, f"v{len(ops)}"))
            elif kind == "delete":
                ops.append((kind, bucket, key, None))
            else:
                ops.append((kind, bucket, key.split("|")[0] + "|", None))
    return ops


def one_by_one(path, ops) -> SQLiteStore:
    """The plain reference: a fresh store fed the ops one call each."""
    ref = SQLiteStore(str(path), synchronous="OFF")
    for kind, bucket, key, value in ops:
        if kind == "put":
            ref.put(bucket, key, value)
        elif kind == "delete":
            ref.delete(bucket, key)
        else:
            ref.delete_prefix(bucket, key)
    return ref


def contents(store) -> dict:
    return {bucket: store.all(bucket) for bucket in BUCKETS}


def low_limit_connect(variables: int):
    """``sqlite3.connect`` whose connections bind ``variables`` at
    most, as a build of SQLite with a low limit would."""
    real = sqlite3.connect

    def connect(*args, **kwargs):
        conn = real(*args, **kwargs)
        conn.setlimit(sqlite3.SQLITE_LIMIT_VARIABLE_NUMBER, variables)
        return conn
    return connect


@pytest.mark.parametrize("n", [0, 1, 2, 511, 512, 513, 2000])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_batch_equals_the_ops_one_by_one(tmp_path, seed, n):
    ops = random_ops(seed * 1000 + n, n)
    batched = SQLiteStore(str(tmp_path / "batched.db"), synchronous="OFF")
    # two batches, so that the second meets rows the first left
    batched.apply_batch(ops[:n // 3])
    batched.apply_batch(ops[n // 3:])
    ref = one_by_one(tmp_path / "ref.db", ops)
    assert contents(batched) == contents(ref)
    assert batched.batch_statements <= max(n, 2)
    batched.close()
    ref.close()


@pytest.mark.parametrize("variables", [4, 20, 999])
def test_rows_a_statement_follow_the_connections_variable_limit(
        tmp_path, monkeypatch, variables):
    monkeypatch.setattr(storage.sqlite3, "connect",
                        low_limit_connect(variables))
    store = SQLiteStore(str(tmp_path / "low.db"), synchronous="OFF")
    monkeypatch.undo()
    assert 3 * store._put_rows <= variables
    assert store._delete_rows + 1 <= variables
    # one row above what a statement may carry, as one run of each kind
    n = max(store._put_rows, store._delete_rows) + 1
    ops = ([("put", "inflight", f"k{i}", f"v{i}") for i in range(n)]
           + [("delete", "inflight", f"k{i}", None) for i in range(1, n)])
    store.apply_batch(ops)
    assert store.all("inflight") == {"k0": "v0"}
    more = random_ops(variables, 600)
    store.apply_batch(more)
    ref = one_by_one(tmp_path / "ref.db", ops + more)
    assert contents(store) == contents(ref)
    store.close()
    ref.close()


def test_later_put_of_a_key_wins_within_one_statement(tmp_path):
    store = SQLiteStore(str(tmp_path / "s.db"), synchronous="OFF")
    store.put("retained", "t", "old")
    store.apply_batch([("put", "retained", "t", "first"),
                       ("put", "retained", "u", "only"),
                       ("put", "retained", "t", "second"),
                       ("delete", "retained", "u", None),
                       ("put", "retained", "u", "again"),
                       ("delete", "retained", "t", None),
                       ("delete", "retained", "t", None)])
    assert store.all("retained") == {"u": "again"}
    # three puts are statements of two rows and of one
    assert store.batch_statements == 5
    store.close()


def test_prefix_delete_is_a_barrier_between_runs(tmp_path):
    store = SQLiteStore(str(tmp_path / "s.db"), synchronous="OFF")
    store.apply_batch([("put", "inflight", "c1|1", "a"),
                       ("put", "inflight", "c[1|1", "glob"),
                       ("put", "inflight", "c2|1", "b"),
                       ("delete_prefix", "inflight", "c1|", None),
                       ("delete_prefix", "inflight", "c[1|", None),
                       ("put", "inflight", "c1|2", "c")])
    assert store.all("inflight") == {"c2|1": "b", "c1|2": "c"}
    assert store.batch_statements == 5
    store.close()


@pytest.mark.parametrize("bad_at", [0, 1, 300, 699])
def test_batch_that_raises_midway_leaves_the_store_as_before(
        tmp_path, bad_at):
    store = SQLiteStore(str(tmp_path / "s.db"), synchronous="OFF")
    store.apply_batch(random_ops(7, 200))
    before, statements = contents(store), store.batch_statements
    ops = random_ops(8, 700)
    # a put with no value breaks the table's NOT NULL in its statement
    ops[bad_at] = ("put", "inflight", "broken", None)
    with pytest.raises(sqlite3.IntegrityError):
        store.apply_batch(ops)
    assert contents(store) == before
    assert store.batch_statements == statements
    store.apply_batch([("put", "inflight", "after", "ok")])
    assert store.get("inflight", "after") == "ok"
    store.close()


@pytest.mark.parametrize("ops", [
    [("put", "inflight", "only", "v")],
    [("put", "inflight", f"k{i}", "v") for i in range(2)],
    [("put", "inflight", f"k{i}", "v") for i in range(400)],
    [("put", "inflight", "first", "v")] + random_ops(5, 399),
], ids=["one-op", "two-ops-one-run", "one-run-of-400", "mixed-400"])
def test_mid_wal_write_lands_inside_the_open_transaction(tmp_path, ops):
    """The kill point is reached once a batch, with a statement
    executed and nothing committed: a second connection sees the store
    as it was, and the connection that would die holds the write."""
    path = str(tmp_path / "s.db")
    store = SQLiteStore(path)
    store.put("inflight", "before", "b")
    seen = []

    def at_kill_point():
        other = sqlite3.connect(path)
        seen.append((store._conn.in_transaction, store._conn.total_changes,
                     other.execute("SELECT key FROM kv").fetchall()))
        other.close()

    faults.REGISTRY.kill_fn = at_kill_point
    faults.arm("crash.at#mid_wal_write", mode="kill", count=2)
    changes = store._conn.total_changes
    store.apply_batch(ops)
    assert len(seen) == 1               # once a batch, not once a statement
    in_transaction, total_changes, visible = seen[0]
    assert in_transaction and total_changes > changes
    assert visible == [("before",)]
    store.close()


def test_record_of_every_byte_class_round_trips_byte_identical(tmp_path):
    """What the journal writes for a delivery, through a multi-row
    statement and back: quotes and escapes, a non-ASCII topic, a 512 B
    payload of every byte value."""
    src = _pub(topic='pl"ant/ü码/\\line\t', payload=bytes(range(256)) * 2,
               qos=1)
    records = {}
    for i, client_id in enumerate(('plain', 'q"uo\\te\n', "ünï-码", "")):
        out = src.delivery(4, 1, False)
        out.packet_id, out.created = i + 1, 1759446000.123456
        records[f"{client_id}|{i + 1}"] = _spliced_record(
            client_id, out, src, held=False)
    assert all(records.values())
    store = SQLiteStore(str(tmp_path / "s.db"))
    store.apply_batch([("put", "inflight", k, v) for k, v in records.items()])
    assert store.all("inflight") == records
    store.close()
    again = SQLiteStore(str(tmp_path / "s.db"))
    assert again.all("inflight") == records
    again.close()


def test_statement_counter_counts_runs_not_ops(tmp_path):
    store = SQLiteStore(str(tmp_path / "s.db"), synchronous="OFF")
    store.apply_batch(
        [("put", "inflight", f"c|{i}", "v") for i in range(400)]
        + [("delete", "inflight", f"c|{i}", None) for i in range(400)])
    # 400 = 256 + 128 + 16 rows an upsert; 400 keys = 256 + 128 + 16
    assert store.batch_statements == 6
    assert store.all("inflight") == {}
    for i in range(800):
        store.apply_batch([("put", "inflight", f"c|{i}", "v")])
    assert store.batch_statements == 6 + 800
    store.put("inflight", "direct", "v")      # no group commit: not counted
    assert store.batch_statements == 6 + 800
    store.close()


def test_statements_total_is_exported_beside_ops_written(tmp_path):
    """``maxmq_storage_statements_total`` reads the backend's counter
    through the journal and the fault shim bootstrap puts between."""
    backend = SQLiteStore(str(tmp_path / "s.db"), synchronous="OFF")
    journal = WriteBehindStore(FaultInjectingStore(backend), batch_ms=0)

    class Hook:
        quarantined = journal_sheds = rewrites_skipped = 0
        store = journal

        def bump_boot_epoch(self):
            return 0

    hook = Hook()
    hook.journal = journal

    class BrokerStub:
        hooks = [hook]
        boot_epoch = storage_barrier_waits = 0

    registry = Registry()
    _register_storage_metrics(registry, BrokerStub())
    for i in range(300):
        journal.put("inflight", f"c|{i}", "v")
    assert journal.flush(timeout=5.0)
    page = registry.expose()
    values = {line.split()[0]: float(line.split()[1])
              for line in page.splitlines()
              if line.startswith("maxmq_storage_")}
    assert values["maxmq_storage_ops_written_total"] == 300
    assert values["maxmq_storage_statements_total"] == backend.batch_statements
    assert 1 <= backend.batch_statements < 300
    journal.close()


@pytest.mark.parametrize("batch_ops", [16, 512],
                         ids=["partial-takes", "whole-queue-takes"])
def test_journal_over_sqlite_under_a_racing_writer_loses_no_write(
        tmp_path, batch_ops):
    """The loop's side enqueues (and coalesces into ops still queued)
    while the writer thread takes batches, the whole queue at once or
    its head: after a flush the backend holds what a dict fed the same
    writes holds. A write coalesced into an op the writer had already
    taken would be lost here."""
    backend = SQLiteStore(str(tmp_path / "s.db"), synchronous="OFF")
    journal = WriteBehindStore(backend, batch_ms=1, batch_ops=batch_ops)
    want = {bucket: {} for bucket in BUCKETS}
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for kind, bucket, key, value in random_ops(99, 30000):
            if kind == "put":
                journal.put(bucket, key, value)
                want[bucket][key] = value
            elif kind == "delete":
                journal.delete(bucket, key)
                want[bucket].pop(key, None)
            else:
                journal.delete_prefix(bucket, key)
                for k in [k for k in want[bucket] if k.startswith(key)]:
                    del want[bucket][k]
        assert journal.flush(timeout=20.0)
    finally:
        sys.setswitchinterval(interval)
    assert contents(backend) == want
    assert journal.queued_bytes_now == 0 and not journal._pending
    assert journal.ops_written + journal.coalesced == 30000
    assert journal.commit_failures == 0
    journal.close()
