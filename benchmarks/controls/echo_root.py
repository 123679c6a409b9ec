"""Control: the post-state root is not computed but taken from the payload
(`compute_post_root` returns the header's own `stateRoot`): the speed that
comes from leaving the root computation out. Every honest block still comes
back VALID with the right root, so only a block whose claimed root is wrong
shows it: a run under it must come out not correct (tampered_root_accepted)."""

import threading


def apply(log):
    """Returns the call that takes the fault out again."""
    import phant_tpu.stateless as stateless

    sound_execute, sound_root = stateless.execute_stateless, stateless.compute_post_root
    claimed = threading.local()

    def execute(chain_id, parent_header, block, *args, **kwargs):
        claimed.root = block.header.state_root
        return sound_execute(chain_id, parent_header, block, *args, **kwargs)

    stateless.execute_stateless = execute
    stateless.compute_post_root = lambda state: claimed.root
    log("CONTROL echo_root: compute_post_root returns the payload's own stateRoot")

    def undo():
        stateless.execute_stateless = sound_execute
        stateless.compute_post_root = sound_root

    return undo
