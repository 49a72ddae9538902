"""The fleets that ``bench/fleets/`` holds build, at the rehearsal size
and a fixed seed, the same rows and payload bytes as the generator did
before each fleet moved to a file of its own: SHA-256 digests of the
prefill blocks and of the encoded payloads of ticks 0-3, taken on the
commit before the move."""
import hashlib

import pytest

import gen
import run
from cells import SEED, checkout, tiny

PINNED = {
    "megascale_12k.incident": (
        "4a2da30be62e84069db0cc1018f9bf479a7451148885d2cee7c5246420180fcc",
        "948b13055beb9d2603dd0cde512d0f24ff73501792a28c232c1d24db5b2bf43f"),
    "hibench_spark_256.steady": (
        "7c33a17da3a4d6e727848fbf5ecb58df583fb8af247e9e7b5e3ba27482640d81",
        "06cee16328b96dcf68c6472273883148bcdb46a6f80f3745090f2950ce07607a"),
}


def block_digest(blocks: list[dict]) -> str:
    h = hashlib.sha256()
    for b in blocks:
        h.update(b["stage"].encode() + b"\0")
        h.update("\0".join(b["task_ids"]).encode() + b"\1")
        h.update("\0".join(b["nodes"]).encode() + b"\1")
        for k in ("starts", "ends", "locality"):
            h.update(b[k].dtype.str.encode() + b[k].tobytes())
        for name, a in b["features"].items():
            h.update(name.encode() + a.dtype.str.encode() + a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_fleet_builds_what_it_built_before_the_move(cell, tmp_path):
    root = checkout(tmp_path)
    _spec, _cell, cfg, traffic = run.load_cell(cell, root)
    cfg = dict(cfg, **tiny(cell)["config"])
    traffic = dict(traffic, **tiny(cell)["traffic"])
    tr = gen.make_traffic(cfg, traffic, SEED, root)
    payloads = hashlib.sha256()
    for k in range(4):
        for p in tr.tick(k):
            payloads.update(gen.encode(*p))
    assert (block_digest(tr.prefill_blocks()),
            payloads.hexdigest()) == PINNED[cell]
