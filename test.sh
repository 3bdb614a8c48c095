#!/usr/bin/env bash
# Run lint (when available) + the full test suite the way CI does.
# Tests force a virtual 8-device CPU mesh themselves (tests/conftest.py);
# JAX_PLATFORMS=cpu keeps any accelerator out of the picture.

set -e
set -x

cd "$(dirname "$0")"

if command -v ruff >/dev/null 2>&1; then
  ruff check rayfed_tpu tests
else
  echo "ruff not installed; skipping lint"
fi

# Concurrency/aggregation contract gate (tool/fedlint): the invariants
# PRs 1-7 paid for — no blocking calls on the event loop, loop-affine
# calls routed threadsafe, no use-after-donate, KeyboardInterrupt/
# SystemExit never swallowed, no seq-id allocation on the comms lane,
# frame-metadata keys declared in wire.py, acyclic lock order — fail CI
# here instead of deadlocking a round three PRs later.  Suppressions
# require an inline pragma with a written reason (FED000 otherwise).
# The dynamic half is the runtime lock-order sanitizer: tests/conftest.py
# exports RAYFED_SANITIZE=1 so the whole pytest run (party subprocesses
# included) raises on lock-order cycles as they form.
python -m tool.fedlint

# Codec-format drift gate: the wire manifest layout is a cross-party
# contract — this fails unless WIRE_FORMAT_VERSION was bumped (and the
# lock re-pinned) whenever the layout changes.
JAX_PLATFORMS=cpu python tool/check_wire_format.py

# Which secure-aggregation suite this host actually exercises: the
# x25519/AES paths need the optional `cryptography` wheel (now part of
# the test/dev extras); without it the stdlib fallback (per-session
# nonce + group key, numpy Philox PRG) is what runs and the
# x25519/AES-specific tests skip LOUDLY — this line makes that skip
# visible in every CI log instead of buried in the pytest summary.
JAX_PLATFORMS=cpu python -c "
from rayfed_tpu.transport import secagg
ka = secagg.KeyAgreement('ci-suite-probe')
print('secagg suite under test: kex=%s prg=%s%s' % (
    ka.kex_scheme, ka.prg_scheme,
    '' if secagg.HAVE_X25519 else
    '  [stdlib fallback — cryptography wheel unavailable; '
    'x25519/AES suite tests will skip loudly]'))
"

JAX_PLATFORMS=cpu python -m pytest tests/ -q "$@"

echo "All tests finished."
