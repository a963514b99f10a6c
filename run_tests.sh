#!/bin/sh
# Run the FULL test suite (including the soak tier) on the virtual 8-device
# CPU platform. The bare `python -m pytest tests/` default excludes soaks
# (pytest.ini addopts) for a fast inner loop; this script clears the marker
# filter so everything runs.
#
# Tests run on the CPU: JAX_PLATFORMS=cpu here, and conftest.py pins it and
# 8 virtual host devices. The chip is reached only through chip_smoke.py.
cd "$(dirname "$0")"

# Build the native components (parser/decoder/percentile/rebuild/ring/tail)
# up front so the suite exercises the C++ fast paths; soft-skip with a
# visible warning when no toolchain — every native consumer degrades to its
# Python fallback (the differential suite covers both).
if make -C native >/dev/null 2>&1; then
    :
else
    echo "WARNING: native build failed or no C++ toolchain;" \
         "parser/decoder fast paths unavailable — Python fallbacks in use" >&2
fi

# --lint: the static-correctness gate, ALL hard requirements (the PR-2
# pyflakes soft-skip is gone): byte-compile everything, run the in-repo
# analyzer (JAX hot-path, lock discipline, config keys, metric catalogue,
# transport headers, durability discipline, pyflakes-lite — see DESIGN.md
# §9) INCLUDING the protocol model checker at its small scopes (the
# delivery/delta-chain/sharded-epoch models verified exhaustively in
# well under 10 s, DESIGN.md §9.4 — a violated invariant prints its
# counterexample schedule and fails the gate), and run real pyflakes when
# the environment ships it (its undefined-name pass goes beyond
# pyflakes-lite; when absent, the in-repo analyzer IS the hard lint
# floor). Consumed standalone (CI lint stage) or before the suite:
# ./run_tests.sh --lint [pytest args...].
if [ "$1" = "--lint" ]; then
    shift
    echo "lint: python -m compileall apmbackend_tpu benchmarks tests"
    python -m compileall -q apmbackend_tpu benchmarks tests || exit 1
    echo "lint: python -m apmbackend_tpu.analysis (rules + small-scope protocol models)"
    python -m apmbackend_tpu.analysis || exit 1
    if python -c "import pyflakes" 2>/dev/null; then
        echo "lint: python -m pyflakes apmbackend_tpu"
        python -m pyflakes apmbackend_tpu || exit 1
    fi
    # --lint alone: stop after linting; with more args fall through to pytest
    [ $# -eq 0 ] && exit 0
fi

# --model: the deep protocol-verification tier — the model checker at its
# deep scopes (larger message counts and fault budgets; minutes, not
# seconds), the full mutation catalogue (every seeded protocol bug must
# yield a counterexample), and the protocol test suite including the
# slow trace-conformance scenarios (kill−9 chaos runs replayed as model
# paths). Run before touching worker.py's epoch cycle, deltachain.py's
# recovery, or any transport's ack semantics:
# ./run_tests.sh --model [pytest args...].
if [ "$1" = "--model" ]; then
    shift
    echo "model: python -m apmbackend_tpu.analysis --models deep (deep scopes + mutants)"
    python -m apmbackend_tpu.analysis -q --models deep || exit 1
    exec env JAX_PLATFORMS=cpu \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_protocol_models.py \
        tests/test_protocol_conformance.py \
        -m "slow or not slow" "$@"
fi

# --sanitize: rebuild every native component with ASan+UBSan (make
# sanitize -> build-sanitize/) and drive the differential fuzz suite and
# the native unit tier against the instrumented parser/percentile/rebuild/
# ring/decoder/tailer — plus the frame-spine suite, so the native APF1
# emitter (apmfrm_pack) packs every codec corpus under instrumentation. libasan/libubsan are LD_PRELOADed so the
# instrumented .so files resolve their runtime inside the stock Python;
# leak detection stays off (CPython+jax hold arenas for the process
# lifetime — interceptor noise, not parser bugs), everything else aborts
# hard so a report can never hide behind a green exit.
if [ "$1" = "--sanitize" ]; then
    shift
    echo "sanitize: make -C native sanitize"
    make -C native sanitize || exit 1
    LIBASAN=$(${CXX:-g++} -print-file-name=libasan.so)
    LIBUBSAN=$(${CXX:-g++} -print-file-name=libubsan.so)
    [ -f "$LIBASAN" ] || { echo "sanitize: libasan.so not found"; exit 1; }
    exec env JAX_PLATFORMS=cpu \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        APM_NATIVE_SANITIZE=1 \
        LD_PRELOAD="$LIBASAN $LIBUBSAN" \
        ASAN_OPTIONS=detect_leaks=0:abort_on_error=1:handle_segv=1 \
        UBSAN_OPTIONS=print_stacktrace=1:halt_on_error=1 \
        python -m pytest tests/test_parser_native_diff.py tests/test_native.py \
        tests/test_frames.py \
        -q -m "not slow" "$@"
fi

# --fleet: the pod-scale sharded-spine tier — the slow multi-process
# scenarios (N real worker shards over a durable spool: kill −9 one shard
# mid-stream with bit-identical recovery, live-traffic quiesced rebalance
# with fleet trace conformance, and the ISSUE 18 self-managing drills:
# watermark-controller convergence on a skewed load then quiet, kill −9
# of the releasing shard mid-move, manager death mid-decision with
# recover(), and the ISSUE 20 query-plane drill: kill −9 one shard under
# live dashboard query load and require partial/stale-marked answers
# from the recorder store with zero 5xx) plus every fast in-process
# fleet test and the query-plane merge/routing suite. Tier-1 keeps only
# the in-process fast paths; run this before touching parallel/fleet.py,
# parallel/rebalancer.py, obs/queryplane.py, the worker's partition
# handoff, or shardmodel.py: ./run_tests.sh --fleet [pytest args...].
if [ "$1" = "--fleet" ]; then
    shift
    exec env JAX_PLATFORMS=cpu \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_fleet.py tests/test_fleet_chaos.py \
        tests/test_queryplane.py \
        tests/test_protocol_models.py \
        -m "slow or not slow" "$@"
fi

# --chaos: the crash-consistency tier explicitly — the kill−9/restart
# subprocess scenarios (marked `slow`, now also asserting crash flight
# bundles are produced and parseable after SIGKILL), the hostile-storage
# matrix (delta-chain torn tails, crash-during-compaction, ENOSPC
# degradation, stale duplicate tails), the spool durability audit, plus
# every fast chaos/at-least-once test and the trace-plane suite (trace
# headers must survive redelivery). Tier-1 runs the fast subset; this
# runs everything.
if [ "$1" = "--chaos" ]; then
    shift
    exec env JAX_PLATFORMS=cpu \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_chaos.py tests/test_chaos_harness.py \
        tests/test_chaos_storage.py tests/test_delta_chain.py \
        tests/test_spool_durability.py \
        tests/test_at_least_once.py tests/test_trace_plane.py \
        tests/test_protocol_conformance.py \
        -m "slow or not slow" "$@"
fi

# --broker: the broker-outage tier — kill/restart the broker mid-stream
# under at-least-once delivery (fake-redis process death, AMQP
# connection-generation churn, durable spool as the no-broker control)
# and prove bit-identical recovery vs a crash-free golden plus bounded
# producer memory throughout, plus the full redis transport suite (the
# real-server tests auto-skip when nothing answers APM_TEST_REDIS_URL)
# and the flow-control spine. Run before touching transport/ send/ack
# paths, the producer pause buffer, or the reconnect/redeliver cycle:
# ./run_tests.sh --broker [pytest args...].
if [ "$1" = "--broker" ]; then
    shift
    exec env JAX_PLATFORMS=cpu \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_broker_outage.py \
        tests/test_redis_transport.py tests/test_flow_control.py \
        tests/test_transport.py tests/test_amqp.py \
        -m "slow or not slow" "$@"
fi

exec env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m pytest tests/ -m "soak or not soak" "$@"
