"""Host time per round spent uploading the channel reports and fetching the
decision, in ms: the benchmark's own host spans around ``device_put`` and
``device_get``, each ending when the copy is done."""


def read(r):
    up, down = r.host.get("upload"), r.host.get("fetch")
    if not up or not down:
        return None
    return 1e3 * (sum(up) / len(up) + sum(down) / len(down))
