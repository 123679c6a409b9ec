"""Control for a replay cell: the post-state root is not computed but taken
on trust (`ReplayEngine.run` switches the chain's own check off before it
runs): the speed that comes from leaving the root walk out, three fifths of
a block. Every honest block still goes through and the final root is still
the reference's, so only a block whose header claims a wrong root shows it: a
run under it must come out not correct (tampered_root_accepted)."""


def apply(log):
    """Returns the call that takes the fault out again."""
    from phant_tpu.replay.engine import ReplayEngine

    sound = ReplayEngine.run

    def run(self, chain, blocks, witnesses=None):
        chain.verify_state_root = False
        return sound(self, chain, blocks, witnesses=witnesses)

    ReplayEngine.run = run
    log("CONTROL replay_skip_root: no block's post-state root is checked against its header")
    return lambda: setattr(ReplayEngine, "run", sound)
