"""Parser correlation tests: SOAP join, entry/exit, TTL backfill, audit trail."""

import math
import os

import pytest

from apmbackend_tpu.ingest.parser import TransactionParser, convert_log_date_to_ms
from apmbackend_tpu.ingest.replay import FixtureGenerator, ReplayDriver, write_fixture_logs
from apmbackend_tpu.ingest.ttlcache import TTLCache

SERVER = "jvmhost1"


def make_parser(records, clock=None):
    kw = {"server_from_path": lambda fp: SERVER}
    if clock is not None:
        kw["clock"] = clock
    return TransactionParser(lambda tx, db: records.append((tx, db)), **kw)


def feed(parser, pairs):
    for fname, line in pairs:
        parser.read_line(fname, line)


def test_ttl_cache_expiry_callback():
    now = [0.0]
    expired = []
    c = TTLCache(10, on_expired=lambda k, v: expired.append(k), clock=lambda: now[0])
    c.set("a", 1)
    assert c.get("a") == 1
    now[0] = 11
    assert c.get("a") is None
    assert expired == ["a"]
    c.set("b", 2)
    now[0] = 30
    assert c.sweep() == 1
    assert expired == ["a", "b"]


def test_soap_ejb_join_with_account():
    records = []
    parser = make_parser(records)
    gen = FixtureGenerator(server=SERVER)
    feed(parser, gen.soap_transaction("getAccountInfo", 500, acct=123456789))
    assert len(records) == 1
    tx, db = records[0]
    assert not db
    assert tx.service == "S:getAccountInfo"
    assert tx.acct_num == 123456789
    assert tx.elapsed == 500
    assert tx.top_level == "Y"
    assert tx.end_ts - tx.start_ts == 500


def test_riskid_two_line_account():
    records = []
    parser = make_parser(records)
    gen = FixtureGenerator(server=SERVER)
    feed(parser, gen.soap_transaction("getRisk", 200, acct=987654321, riskid=True))
    assert len(records) == 1
    assert records[0][0].acct_num == 987654321


def test_standard_ct_with_baf_salvage():
    """No SOAP account: the exit line's BAF metadata is the salvage source.

    Reference semantics: the record parks in the needNum cache with the
    salvaged altAcctNum and is emitted at TTL expiry (the salvage primes the
    acct cache for later exits of the same logId, not the current one —
    stream_parse_transactions.js:542-560, :226-239)."""
    now = [0.0]
    records = []
    parser = make_parser(records, clock=lambda: now[0])
    gen = FixtureGenerator(server=SERVER)
    feed(parser, gen.standard_ct_transaction("getOffers", 300, acct=555000111, baf_meta=True))
    assert records == []  # parked
    now[0] = 31
    parser.sweep()
    assert len(records) == 1
    tx, db = records[0]
    assert tx.acct_num == 555000111
    assert tx.service == "getOffers"
    assert tx.top_level == "N"


def test_baf_salvage_primes_acct_for_second_exit():
    """A second exit on the same logId finds the salvaged number immediately."""
    records = []
    parser = make_parser(records)
    log_id = "jbX"
    meta = "[ch:7:444555666]"
    parser.read_line(
        "app_x.log",
        f"[{log_id}] 2024-01-10 09:00:00,000 {meta} INFO CommonTiming::Start svcA begin",
    )
    parser.read_line(
        "app_x.log",
        f"[{log_id}] 2024-01-10 09:00:00,300 {meta} INFO CommonTiming::Stop svcA completed in time: 300 ms",
    )
    parser.read_line(
        "app_x.log",
        f"[{log_id}] 2024-01-10 09:00:00,400 {meta} INFO CommonTiming::Start svcB begin",
    )
    parser.read_line(
        "app_x.log",
        f"[{log_id}] 2024-01-10 09:00:00,900 {meta} INFO CommonTiming::Stop svcB completed in time: 500 ms",
    )
    # svcB exits after svcA's salvage primed the acct cache -> immediate emit;
    # svcA itself stays parked (the salvage's backfill check ran before svcA
    # was parked) and surfaces on expiry — reference ordering quirk
    assert len(records) == 1
    assert records[0][0].service == "svcB"
    assert records[0][0].acct_num == 444555666


def test_missing_account_parks_then_backfills():
    """Exit before SOAP account: record parks in needNum cache, then the SOAP
    account line releases it (saveAcctNum backfill path)."""
    records = []
    parser = make_parser(records)
    gen = FixtureGenerator(server=SERVER)
    pairs = gen.soap_transaction("getFoo", 400, acct=111222333)
    soap_lines = [p for p in pairs if p[0].startswith("soap")]
    server_lines = [p for p in pairs if p[0] == "server.log"]
    # deliver timing lines FIRST (account unknown), but keep the SOAP IO=I
    # header first so the context exists
    feed(parser, soap_lines[:1])
    feed(parser, server_lines)
    assert records == []  # parked, waiting for the number
    feed(parser, soap_lines[1:])
    assert len(records) == 1
    assert records[0][0].acct_num == 111222333


def test_missing_account_expires_and_emits_numberless():
    now = [0.0]
    records = []
    parser = make_parser(records, clock=lambda: now[0])
    gen = FixtureGenerator(server=SERVER)
    pairs = gen.soap_transaction("getBar", 250)  # no account anywhere
    feed(parser, pairs)
    assert records == []
    now[0] = 31  # past needNum TTL (30 s)
    parser.sweep()
    assert len(records) == 1
    tx, _ = records[0]
    assert math.isnan(tx.acct_num)
    assert tx.elapsed == 250


def test_partial_without_exit_discarded():
    now = [0.0]
    records = []
    parser = make_parser(records, clock=lambda: now[0])
    parser.read_line(
        "server.log",
        "[jb1] 2024-01-10 09:00:00,000 INFO [CommonTiming] The EJB timing entry has begun for method getLost",
    )
    now[0] = 121
    parser.sweep()
    assert records == []  # discarded, not emitted


def test_exit_without_entry_emits_incomplete():
    records = []
    parser = make_parser(records)
    parser.read_line(
        "server.log",
        "[jb9] 2024-01-10 09:00:01,000 INFO [CommonTiming] Total time for EJB getOrphan call: 123 ms",
    )
    assert len(records) == 1
    tx, _ = records[0]
    assert tx.service == "S:getOrphan"
    assert tx.log_id == ""
    assert tx.elapsed == 123
    assert tx.start_ts == tx.end_ts - 123  # start backfilled from elapsed


def test_audit_trail_multi_subservice():
    records = []
    parser = make_parser(records)
    gen = FixtureGenerator(server=SERVER)
    feed(parser, gen.audit_trail(
        [("Provider[credit-check]", 120), ("bcottag", 10), ("bcottag", 20)], acct=999888777
    ))
    assert len(records) == 3
    services = [r[0].service for r in records]
    assert services == ["Provider:credit-check", "bcottag", "bcottag"]
    # Provider goes to the stats pipeline; others straight to DB
    assert [r[1] for r in records] == [False, True, True]
    # repeated subservice consumed FIFO: elapsed 10 then 20
    assert records[1][0].elapsed == 10 and records[2][0].elapsed == 20
    assert all(r[0].acct_num == 999888777 for r in records)


def test_provider_normalization_case_insensitive():
    now = [0.0]
    records = []
    parser = make_parser(records, clock=lambda: now[0])
    gen = FixtureGenerator(server=SERVER)
    feed(parser, gen.standard_ct_transaction("provider[x-y]", 100, acct=1, baf_meta=True))
    now[0] = 31
    parser.sweep()
    assert records[0][0].service == "Provider:x-y"


def test_fixture_replay_end_to_end(tmp_path):
    paths = write_fixture_logs(str(tmp_path), n_transactions=100, seed=3)
    records = []
    parser = TransactionParser(
        lambda tx, db: records.append(tx), server_from_path=lambda fp: SERVER
    )
    drv = ReplayDriver(parser)
    drv.feed_dir(str(tmp_path))
    drv.finish()
    assert drv.lines_fed > 300
    # every generated transaction produced at least one record
    assert len(records) >= 100
    with_acct = [r for r in records if not math.isnan(r.acct_num)]
    assert len(with_acct) / len(records) > 0.9  # correlation succeeded broadly
    # timestamps sane: elapsed == end - start whenever both present
    for r in records:
        if not math.isnan(r.start_ts):
            assert r.end_ts - r.start_ts == r.elapsed


def test_log_date_conversion():
    assert convert_log_date_to_ms("") == ""
    iso = convert_log_date_to_ms("2020-01-07T10:00:01.959-06:00")
    assert iso == str(int(1578412801959))
    std = convert_log_date_to_ms("2020-01-07 10:00:02,669")
    assert std.isdigit() and len(std) == 13


def test_malformed_lines_never_fatal():
    """Truncated/binary/garbage lines are skipped, parser keeps working."""
    records = []
    parser = make_parser(records)
    for line in [
        "complete garbage %$#@!",
        "[jb1] 2024-01-10 09:00:00,000 INFO [CommonTiming] The EJB",  # truncated
        "\x00\x01\x02 binary junk",
        "[jb2] not-a-date INFO [CommonTiming] Total time for EJB x call: abc ms",
        "Audit Trail id :",  # empty autr id
    ]:
        parser.read_line("server.log", line)
        parser.read_line("app_x.log", line)
    parser.read_line(
        "server.log",
        "[jb9] 2024-01-10 09:00:01,000 INFO [CommonTiming] Total time for EJB alive call: 10 ms",
    )
    assert records and records[-1][0].service == "S:alive"


def test_consumer_error_distinguished(caplog):
    import logging

    def bad_consumer(tx, db):
        raise RuntimeError("sink exploded")

    parser = TransactionParser(bad_consumer, server_from_path=lambda fp: SERVER)
    parser.logger = logging.getLogger("t")
    with caplog.at_level(logging.ERROR):
        parser.read_line(
            "server.log",
            "[jb9] 2024-01-10 09:00:01,000 INFO [CommonTiming] Total time for EJB x call: 10 ms",
        )
    assert any("Record consumer failed" in r.message for r in caplog.records)
    assert not any("Unparseable" in r.message for r in caplog.records)


def test_marker_cooccurrence_keeps_ladder_priority():
    """A line where two timing markers CO-OCCUR must dispatch by the
    reference's sequential ladder priority (EJB entry > EJB exit > CT
    start > CT stop), not by leftmost occurrence — the alternation scan is
    only a pre-filter (parser.py _SERVER_DISPATCH_RE note).

    Construct a line whose LOWER-priority marker appears FIRST: leftmost
    dispatch would pick the exit handler; the ladder must pick entry."""
    records = []
    parser = make_parser(records)
    # 'Total time' (exit marker) textually precedes 'The EJB' (entry
    # marker); ladder priority says EJB ENTRY wins. Token layout satisfies
    # _parse_ejb_entry (service at arr[13]).
    line = ("[jbX] 2024-01-10 09:00:00,000 pre INFO [CommonTiming] Total time "
            "noise INFO [CommonTiming] The EJB svcY call")
    parser.read_line("server.log", line)
    # entry parks a partial (no emission); a ladder regression dispatching
    # the exit handler would emit an unmatched-exit record immediately
    assert records == []
    # a later exit for the same logId but a DIFFERENT service token: the
    # join deliberately misses (the parked 'S:svcY' partial stays cached)
    # and the unmatched-exit path emits — pinning that the co-occurrence
    # line produced no emission of its own
    parser.read_line(
        "server.log",
        "[jbX] 2024-01-10 09:00:02,000 INFO [CommonTiming] Total time for "
        "EJB INFO call: 17 ms",
    )
    assert len(records) == 1
    assert records[0][0].service == "S:INFO"  # the unmatched-exit record
    assert parser.record_cache.get("jbX") and "S:svcY" in parser.record_cache.get("jbX")


def test_app_log_ejb_marker_falls_through_to_app_state():
    """APP logs only dispatch CT handlers; a line carrying an EJB marker
    (leftmost) plus no CT marker must fall through to the audit-trail state
    machine, exactly like the reference's APP branch."""
    records = []
    parser = make_parser(records)
    # 14+ tokens so a wrongly-dispatched _parse_ejb_entry would SUCCEED and
    # park a partial (an 8-token line would just raise-and-swallow, which
    # records==[] cannot distinguish from correct fall-through)
    line = ("[jb1] 2024-01-10 09:00:00,000 a b c INFO [CommonTiming] "
            "The EJB is named svcZ here")
    parser.read_line("app_1.log", line)
    assert records == []
    # DISCRIMINATING check: correct fall-through parks nothing; the EJB
    # handler regression would have cached a partial under logId jb1
    assert parser.record_cache.get("jb1") is None
    assert parser.cache_stats()["record"]["keys"] == 0


def _lock_stream(server, n_tx=1500, seed=7):
    """(file, chunk) steps of a mixed stream from one server whose every
    record comes out the same whenever the need-number sweep runs:
    accounts arrive before the exits they join, and a parked record is
    released carrying the account it was parked with."""
    import random

    gen = FixtureGenerator(server=server, seed=seed)
    gen._next_id = seed * 10**6  # log ids unique across servers
    rng = random.Random(seed)
    steps = []
    for i in range(n_tx):
        kind = rng.random()
        svc = f"svc{rng.randrange(8)}"
        if kind < 0.3:
            pairs = gen.soap_transaction(svc, rng.randint(50, 1200), acct=10**8 + i)
        elif kind < 0.6:  # no account: parked for the 30 s TTL
            pairs = gen.soap_transaction(svc, rng.randint(50, 1200))
        elif kind < 0.85:  # BAF metadata account: parked with it
            pairs = gen.standard_ct_transaction(svc, rng.randint(50, 1200), acct=10**8 + i,
                                                baf_meta=True)
        else:
            pairs = gen.audit_trail([(f"Provider[{svc}]", rng.randint(5, 50)),
                                     ("bcottag", rng.randint(5, 50))], acct=10**8 + i)
        gen.advance(0.05)
        for fname, line in pairs:
            fname = f"{server}/{fname}"
            if steps and steps[-1][0] == fname:
                steps[-1][1].append(line)
            else:
                steps.append((fname, [line]))
    return [(f, ("\n".join(lines) + "\n").encode()) for f, lines in steps]


@pytest.mark.parametrize("feeders", [1, 4])
@pytest.mark.parametrize("no_native", ["", "1"], ids=["native", "python"])
def test_sweep_thread_beside_the_feed_changes_no_record(monkeypatch, no_native, feeders):
    """The cache-sweep timer runs on its own thread beside the feed, and
    the live tail path feeds from one pump thread per file; the parser
    lock keeps them out of each other's chunks (ctypes releases the GIL
    inside the native engine, so the GIL alone would not)."""
    import sys
    import threading

    from apmbackend_tpu.obs.attrib import AttributionPlane, set_attrib

    monkeypatch.setenv("APM_PARSE_NO_NATIVE", no_native)
    streams = [_lock_stream(f"jvm{k}", n_tx=1500 // feeders, seed=7 + k) for k in range(feeders)]
    # past the 30 s TTL over the whole feed, inside the 120 s ones
    per_step = 90.0 / sum(len(steps) for steps in streams)

    def run(concurrent):
        now = [1000.0]
        records = []
        parser = TransactionParser(lambda tx, db: records.append((tx.to_csv(), db)),
                                   server_from_path=lambda fp: fp.split("/")[0],
                                   clock=lambda: now[0])
        tick = threading.Lock()
        stop, errors = threading.Event(), []

        def feed(steps):
            try:
                for fname, chunk in steps:
                    parser.read_lines(fname, chunk)
                    with tick:
                        now[0] += per_step
            except Exception as e:  # pragma: no cover - the failure reported below
                errors.append(e)

        def sweeper():
            try:
                while not stop.is_set():
                    parser.sweep()
            except Exception as e:  # pragma: no cover - the failure reported below
                errors.append(e)

        if not concurrent:
            for steps in streams:
                feed(steps)
        else:
            sweep_thread = threading.Thread(target=sweeper, daemon=True)
            feed_threads = [threading.Thread(target=feed, args=(steps,), daemon=True)
                            for steps in streams]
            sweep_thread.start()
            for t in feed_threads:
                t.start()
            for t in feed_threads:
                t.join(timeout=60)
            stop.set()
            sweep_thread.join(timeout=30)
            assert not any(t.is_alive() for t in feed_threads + [sweep_thread])
        parser.drain()
        return parser, records, errors

    old_plane = set_attrib(AttributionPlane(module="w"))
    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        parser, records, errors = run(concurrent=True)
        st = parser._att_sweep.snapshot()
        _, expected, _ = run(concurrent=False)
    finally:
        sys.setswitchinterval(old_interval)
        set_attrib(old_plane)
    assert (parser._native is None) == (no_native == "1")
    assert errors == []
    assert st["failed"] == 0 and st["events"] > 0 and st["released"] > 0
    assert sorted(records) == sorted(expected)
    assert len(expected) > 1500
