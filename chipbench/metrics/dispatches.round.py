"""Client-phase dispatches per round: ``RoundReport.dispatches`` (a count
the program keeps), averaged over the window's rounds."""


def read(rec):
    if rec.unit != "round" or not rec.reports:
        return None
    return sum(int(r.dispatches) for r in rec.reports) / len(rec.reports)
