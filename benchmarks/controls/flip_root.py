"""Control: the timed path broken where the answer is produced: every
third VALID reply carries a post-state root with one bit flipped. A run
under it must come out not correct (wrong_root > 0)."""

import itertools


def apply(log):
    """Returns the call that takes the fault out again."""
    from phant_tpu.engine_api import StatelessPayloadStatusV1

    sound = StatelessPayloadStatusV1.to_json
    tick = itertools.count()

    def to_json(self):
        out = sound(self)
        if out["status"] == "VALID" and next(tick) % 3 == 0:
            last = out["stateRoot"][-1]
            out["stateRoot"] = out["stateRoot"][:-1] + ("1" if last == "0" else "0")
        return out

    StatelessPayloadStatusV1.to_json = to_json
    log("CONTROL flip_root: every third VALID reply carries an altered root")
    return lambda: setattr(StatelessPayloadStatusV1, "to_json", sound)
