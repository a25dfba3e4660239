"""Time per merge outside the executor (api.session / api.service: planning,
the scheduling window, catalog): each job's share of its batch's wall time
less the job's executor seconds, averaged over the window's jobs."""


def read(run):
    gaps = [b["wall_s"] / len(b["jobs"]) - j["seconds"]
            for b in run["batches"] for j in b["jobs"]]
    return sum(gaps) / len(gaps) if gaps else None
