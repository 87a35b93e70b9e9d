"""AppCircuit lifecycle: build -> pin -> keygen -> prove -> verify.

Reference parity: the `AppCircuit` trait (`util/circuit.rs:86-239`):
staged circuit creation (keygen from a default witness, prover from pinning),
pk caching, proof generation. The TPU/CPU backend choice threads through to
the plonk prover (BASELINE.json north star's `--backend` selection).
"""

from __future__ import annotations

import os
import pickle

from ..builder import Context
from ..observability.tracing import span
from ..plonk import backend as B
from ..plonk.keygen import ProvingKey, keygen
from ..plonk.mock import mock_prove
from ..plonk.prover import prove as plonk_prove
from ..plonk.srs import SRS
from ..plonk.verifier import verify as plonk_verify
from ..utils.pinning import Pinning

BUILD_DIR = os.environ.get("BUILD_DIR", os.path.join(
    os.path.dirname(__file__), "..", "..", "build"))


class AppCircuit:
    """Subclasses define: name, default_lookup_bits, build(ctx, args, spec) ->
    list of instance AssignedValues (already exposed), and
    get_instances(args, spec) -> native public inputs."""

    name = "app"
    default_lookup_bits = 8

    # -- to implement ---------------------------------------------------
    @classmethod
    def build(cls, ctx: Context, args, spec):
        raise NotImplementedError

    @classmethod
    def get_instances(cls, args, spec) -> list:
        raise NotImplementedError

    # -- lifecycle ------------------------------------------------------
    @classmethod
    def build_context(cls, args, spec, **kwargs) -> Context:
        """Witness generation with the cyclic GC paused: builder structures
        hold no reference cycles, and gen-2 collections over tens of
        millions of cells turn an ~6-minute build into >30 minutes."""
        import gc
        ctx = Context()
        was_enabled = gc.isenabled()
        gc.disable()
        try:
            cls.build(ctx, args, spec, **kwargs)
        finally:
            if was_enabled:
                gc.enable()
        return ctx

    @classmethod
    def pinning_path(cls, spec, k: int) -> str:
        return os.path.join(BUILD_DIR, f"{cls.name}_{spec.name}_{k}.pinning.json")

    @classmethod
    def create_pk(cls, srs: SRS, spec, k: int, dummy_args, bk=None,
                  cache: bool = True):
        """Keygen from a default witness; pin the shape; cache pk to disk
        (reference: pk written next to pinning, `util/circuit.rs:130-136`).
        dummy_args may be a zero-arg callable, evaluated only on cache miss."""
        bk = bk or B.get_backend()
        pk_path = os.path.join(BUILD_DIR, f"{cls.name}_{spec.name}_{k}.pk")
        pin_path = cls.pinning_path(spec, k)
        if cache and os.path.exists(pk_path) and os.path.exists(pin_path):
            with open(pk_path, "rb") as f:
                return pickle.load(f)
        if callable(dummy_args):
            # lazy: aggregation dummy args cost a full inner proof — only
            # pay it on a cache miss
            dummy_args = dummy_args()
        ctx = cls.build_context(dummy_args, spec)
        pin = Pinning.load_or_create(pin_path, ctx, k, cls.default_lookup_bits)
        asg = ctx.assignment(pin.config)
        pk = keygen(srs, pin.config, asg.fixed, asg.selectors, asg.copies, bk)
        if cache:
            os.makedirs(BUILD_DIR, exist_ok=True)
            with open(pk_path, "wb") as f:
                pickle.dump(pk, f)
        return pk

    @classmethod
    def mock(cls, args, spec, k: int) -> bool:
        import gc
        ctx = cls.build_context(args, spec)
        was_enabled = gc.isenabled()
        gc.disable()     # same no-cycles argument as build_context
        try:
            cfg = ctx.auto_config(k=k, lookup_bits=cls.default_lookup_bits)
            return mock_prove(cfg, ctx.assignment(cfg))
        finally:
            if was_enabled:
                gc.enable()

    @classmethod
    def prove(cls, pk: ProvingKey, srs: SRS, args, spec, bk=None,
              transcript=None) -> bytes:
        """transcript: None = Blake2b; pass PoseidonTranscript() for
        aggregation-bound snarks, KeccakTranscript() for the EVM path
        (reference: gen_snark_shplonk vs gen_evm_proof_shplonk)."""
        with span("job/witness"):
            ctx = cls.build_context(args, spec)
            asg = ctx.assignment(pk.vk.config)
        return plonk_prove(pk, srs, asg, bk, transcript=transcript)

    @classmethod
    def verify(cls, vk, srs: SRS, instances, proof: bytes) -> bool:
        return plonk_verify(vk, srs, [instances], proof)
