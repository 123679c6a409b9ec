"""Control: the verifier put out of the program's place by one that trusts
the payload (no witness check, no execution: VALID with the header's own
state root). It breaks the guarantees that a tampered witness and a tampered
signature are INVALID; a run under it must come out not correct."""


def apply(log):
    """Returns the call that takes the fault out again."""
    import phant_tpu.stateless as stateless

    sound = stateless.execute_stateless

    def trusting(chain_id, parent_header, block, pre_state_root, nodes, codes, **_kw):
        return None, block.header.state_root

    stateless.execute_stateless = trusting
    log("CONTROL trust_header: execute_stateless answers VALID with the claimed root")
    return lambda: setattr(stateless, "execute_stateless", sound)
