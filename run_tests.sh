#!/bin/bash
# Canonical test invocation: hermetic CPU jax with 8 virtual devices
# (JAX_PLATFORMS=cpu keeps the tests off any attached accelerator; see
# tests/conftest.py, which sets the same for bare pytest runs).
#
# Builds native/ first: without libaf2data.so the 14 C++-loader tests
# silently skip, and a canonical run must not under-test. A missing
# toolchain fails LOUDLY; export AF2TPU_SKIP_NATIVE=1 to opt out explicitly
# on toolchain-less hosts.
set -e
# resolve caller-relative test paths BEFORE cd'ing to the repo root, so
# `run_tests.sh ../foo/test_x.py` keeps working from any directory
ARGS=()
for a in "$@"; do
  if [[ "$a" != -* && -e "$a" ]]; then
    a="$(cd "$(dirname "$a")" && pwd)/$(basename "$a")"
  fi
  ARGS+=("$a")
done
cd "$(dirname "$0")"

# -O strips asserts: load-bearing checks on user-facing library paths must
# be raises, not asserts. Allowed: tests/ (pytest idiom)
# and trace-time asserts inside Pallas kernel bodies (never run under -O'd
# user code — they execute at jit trace, and the kernels assert only on
# programmer-error block math).
if grep -rn --include='*.py' -E '^[[:space:]]*assert ' \
    alphafold2_tpu/ --exclude-dir=__pycache__ \
    | grep -v 'ops/pallas/' ; then
  echo "run_tests.sh: load-bearing 'assert' on a library path (use raise;" >&2
  echo "python -O strips asserts into silent wrong math). See above." >&2
  exit 1
fi

if [ "${AF2TPU_SKIP_NATIVE}" != "1" ]; then
  command -v "${CXX:-g++}" >/dev/null || {
    echo "run_tests.sh: ${CXX:-g++} not found — native/ cannot build, and" >&2
    echo "without libaf2data.so 14 loader tests silently skip. Install a" >&2
    echo "C++ toolchain (or export CXX) or set AF2TPU_SKIP_NATIVE=1 to" >&2
    echo "accept the skips." >&2
    exit 1
  }
  make -C native all >/dev/null
fi
exec env JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -m pytest "${ARGS[@]:-tests/}" -q
