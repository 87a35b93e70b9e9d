"""How a served committee-update proof is judged: the verifying key the
service holds hashes to the digest the configuration pins (the circuit that
is proved is part of the deployment); the public inputs are the Poseidon
commitment to the request's committee (instance 0, recomputed here from the
request's keys by reference/poseidon.py) and the finalized header's root
(instances 1 and 2, which the request generator worked out from the
header); the proof verifies for them under the plain verifier."""

from __future__ import annotations

from reference import plonk, poseidon


class Reference:
    def __init__(self, config: dict, vk_numbers: dict):
        self.vk = plonk.VerifyingKey(vk_numbers)
        self.digest = self.vk.digest().hex()
        self.vk_ok = self.digest == config["vk_digest"]
        self.tau = plonk.unsafe_tau(config["srs"]["seed"])
        self.offset = 12 if config.get("compress") else 0

    def check(self, request: dict, result: dict) -> str:
        if not self.vk_ok:
            return (f"verifying key digest {self.digest} is not the "
                    f"configuration's")
        try:
            proof = bytes.fromhex(result["proof"][2:])
            instances = [int(v, 16) for v in result["instances"]]
        except (KeyError, ValueError, TypeError) as exc:
            return f"unreadable result: {exc}"
        committee = request["params"]["light_client_update"][
            "next_sync_committee"]["pubkeys"]
        expected = dict(request["expected_instances"])
        expected[0] = poseidon.committee_commitment(
            [bytes.fromhex(pk[2:]) for pk in committee])
        for pos, want in sorted(expected.items()):
            if instances[self.offset + pos] != want:
                return f"public input {pos} is not the request's"
        if int(result.get("committee_poseidon", "0x0"), 16) \
                != instances[self.offset]:
            return "committee_poseidon is not the proof's public input"
        return plonk.verify(self.vk, self.tau, [instances], proof)
