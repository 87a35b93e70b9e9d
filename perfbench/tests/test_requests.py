"""The benchmark's own request generator against the program's parser: a
generated update passes the preprocessor's branch check, and the public
inputs the reference expects (the committee's Poseidon commitment, which
the judge recomputes, and the header's root) are the ones the circuit
exposes."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))

import pytest  # noqa: E402

from harness import cells  # noqa: E402

SIZES = {"sync_committee_size": 32, "sync_committee_pubkeys_depth": 6,
         "sync_committee_pubkeys_root_index": 110}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_generated_update_is_valid(seed):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from spectre_tpu import spec as spec_mod
    from spectre_tpu.models import CommitteeUpdateCircuit
    from spectre_tpu.preprocessor.rotation import rotation_args_from_update
    make = cells.load_plugin("requests", "committee_update").make
    spec = spec_mod.SPECS["minimal"]
    for key, val in SIZES.items():
        assert getattr(spec, key) == val
    req = make({"spec_sizes": SIZES}, seed, 0)
    args = rotation_args_from_update(req["params"]["light_client_update"],
                                     spec)
    inst = CommitteeUpdateCircuit.get_instances(args, spec)
    assert {1: inst[1], 2: inst[2]} == req["expected_instances"]
    # public input 0: the reference's plain Poseidon against the program's
    from reference import poseidon
    keys = [bytes.fromhex(k[2:]) for k in req["params"][
        "light_client_update"]["next_sync_committee"]["pubkeys"]]
    assert poseidon.committee_commitment(keys) == inst[0]


def test_requests_differ_and_repeat():
    make = cells.load_plugin("requests", "committee_update").make
    cfg = {"spec_sizes": dict(SIZES, sync_committee_size=2)}
    a, b = make(cfg, 5, 0), make(cfg, 5, 1)
    assert a["params"] != b["params"]
    assert a == make(cfg, 5, 0)
    assert make(cfg, 5, "warmup")["params"] != a["params"]
