#!/bin/sh
# CI entry (reference analog: paddle/scripts/paddle_build.sh): what no
# other command does.  Native build, the test suite as the driver runs
# it (tier 1: -m 'not slow', six workers, a file a worker) and then the
# slow tests (the real-subprocess chaos of tests/test_gang.py,
# tests/test_preempt.py and others), the API-stability diff, the
# multichip dry run.  Everything here runs on the CPU; the chip is
# reached only through the chip tool (.claude/skills/verify/SKILL.md),
# and speed is benchmarks/run.py's to say (BENCHMARK.json, PERF.md).
set -e
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1

echo "== native components =="
sh paddle_tpu/native/build.sh
sh paddle_tpu/native/build_demo.sh

echo "== tests (virtual 8-device CPU mesh) =="
python -m pytest tests/ -q -m 'not slow' -p no:cacheprovider \
    -p xdist -n 6 --dist loadfile
python -m pytest tests/ -q -m slow -p no:cacheprovider

echo "== API stability =="
python tools/diff_api.py

echo "== multichip dryrun (8 virtual devices) =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -c "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"

echo "CI OK"
