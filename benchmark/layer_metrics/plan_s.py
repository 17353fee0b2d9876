"""lifecycle / planner: mean `plan` segment of the lifecycle timeline
(GET progressUri, session lifecycle=on in the traced run only)."""


def read(run):
    plans = [s["progress"]["segments"]["plan"] for s in run["completed"]
             if (s.get("progress") or {}).get("segments", {}).get("plan")
             is not None]
    return sum(plans) / len(plans) if plans else None
