"""The checkpointer's encode seconds (np.save + CRC of each leaf) per save
in the window, from AssiseCheckpointer.stats."""


def read(run):
    if not run.saves:
        return None
    return sum(s["stats"]["encode_s"] for s in run.saves) / len(run.saves)
