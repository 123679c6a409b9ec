"""A whole run of the harness, less its look for a chip (`--rehearse`), at a
tiny genesis: sound, it comes out correct; with the answer altered where it
is produced, with the post-state root echoed from the payload and not
computed, or with the verifier put out of place by one that trusts the
payload, it comes out not correct. The test steers the configuration onto
the cpu crypto backend (12 s a request otherwise, on XLA's CPU ecrecover)."""

import json

import pytest

import run


def _result(capsys, monkeypatch, control):
    load = run.load_json

    def steered(path):
        out = load(path)
        if path.name == "serve-mpt-1chip.json":
            out["argv"] = ["--crypto_backend=cpu", "--evm_backend=native", "--engine_api_port", "0"]
        return out

    monkeypatch.setattr(run, "load_json", steered)
    argv = ["--workload", "serve-mpt-1chip.lone", "--seed", "77", "--seconds", "12", "--trace", "0", "--rehearse"]
    if control:
        argv += ["--control", control]
    assert run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize(
    "control,number",
    [
        (None, None),
        ("flip_root", "wrong_root"),
        ("echo_root", "tampered_root_accepted"),
        ("trust_header", "tampered_witness_accepted"),
        ("trust_header", "tampered_receipts_accepted"),
    ],
)
def test_run_is_correct_only_when_sound(capsys, monkeypatch, control, number):
    if control == "flip_root":
        from phant_tpu.engine_api import StatelessPayloadStatusV1

        monkeypatch.setattr(StatelessPayloadStatusV1, "to_json", StatelessPayloadStatusV1.to_json)
    if control in ("trust_header", "echo_root"):
        import phant_tpu.stateless as stateless

        monkeypatch.setattr(stateless, "execute_stateless", stateless.execute_stateless)
        monkeypatch.setattr(stateless, "compute_post_root", stateless.compute_post_root)
    r = _result(capsys, monkeypatch, control)
    assert list(r)[-1] == "compared"
    if control is None:
        assert r["correct"] is True and r["failed"] == 0
        assert r["metrics"]["blocks_per_s"]["value"] > 0
        assert r["device"]["platform"] == "cpu"
    else:
        assert r["correct"] is False and r["failed"] > 0
        c = r["compared"][number]
        assert c["value"] > c["limit"]
        if control == "echo_root":  # nothing else sees a root that is not computed
            others = {n: c for n, c in r["compared"].items() if c["is"] == "at_most" and n != number}
            assert all(c["value"] <= c["limit"] for c in others.values()), others
