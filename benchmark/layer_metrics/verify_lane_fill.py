"""Real signatures over lanes launched: the sum of the verify rows'
``lanes`` over the sum of their ``bucket`` (the padded batch the program
ran), over the window.  What the bucket rule wastes on a stream of
ragged blocks; 80.8 % for the cutter's cycle under powers of two to 512
and multiples of 512 above.  A count.  None where the launch ledger's
verify rows carry no ``bucket``."""

LAYER, UNIT, SOURCE, MOVES = ("validator.device_lane", "%",
                              "program_counter", "commit_tx_per_s")


def read(obs):
    rows = [r for r in obs.launch_rows
            if r["kernel"] == "verify" and r.get("bucket")]
    if not rows:
        return None
    return (sum(r["lanes"] for r in rows)
            / sum(r["bucket"] for r in rows) * 100.0)
