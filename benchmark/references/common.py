"""What the plain references share."""


def walk(path_obj, pred: str) -> list[int]:
    """The uids along one `_path_` entry, which nests single objects:
    {"uid": ..., pred: {"uid": ..., pred: {...}}}."""
    hops, cur = [], path_obj
    while cur is not None:
        hops.append(int(cur["uid"], 16))
        nxt = cur.get(pred)
        cur = nxt[0] if isinstance(nxt, list) else nxt
    return hops
