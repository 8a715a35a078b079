"""Golden fixture for the cache-invalidation checker: writes to what a route
snapshot holds, with and without the `bump=` that moves its token with them."""


class FakeController:
    def __init__(self, store):
        self.store = store
        self.meta_store = store

    def upload_without_bump(self, table, seg):
        ideal = self.store.get(f"/tables/{table}/idealstate") or {}
        ideal[seg] = ["s1"]
        self.store.set(f"/tables/{table}/idealstate", ideal)  # line 13: VIOLATION

    def refresh_without_bump(self, table, seg, meta):
        self.meta_store.update(  # line 16: VIOLATION
            f"/tables/{table}/segments/{seg}", lambda cur: meta
        )

    def upload_with_bump(self, table, seg):
        self.store.set(f"/tables/{table}/idealstate", {seg: ["s1"]})  # line 21: VIOLATION, the count is a step of its own
        self.bump_routing_version(table)

    def bump_routing_version(self, table):
        doc = self.store.update(  # CLEAN: the sanctioned version writer
            f"/tables/{table}/routingversion",
            lambda cur: {"v": int((cur or {}).get("v", 0)) + 1},
        )
        return int(doc["v"])

    def read_only_paths(self, table):
        self.store.get(f"/tables/{table}/idealstate")  # CLEAN: read, not write
        self.store.set(f"/tables/{table}/quota", {"qps": 1})  # CLEAN: not segment-set
        self.caches.set(f"/tables/{table}/idealstate", {})  # CLEAN: not a store receiver

    def suppressed_write(self, table):
        self.store.set(f"/tables/{table}/idealstate", {})  # pinotlint: disable=cache-invalidation — fixture: bump lives in the caller

    def write_that_counts(self, table, seg, meta):
        counter = f"/tables/{table}/routingversion"
        self.store.set(f"/tables/{table}/segments/{seg}", meta, bump=counter)  # CLEAN
        self.store.update(f"/tables/{table}/idealstate", lambda cur: cur, bump=counter)  # CLEAN
        self.store.delete(f"/tables/{table}/segments/{seg}", bump=counter)  # CLEAN

    def other_routing_state(self, table, sid, doc):
        self.store.set(f"/schemas/{table}", doc)  # line 46: VIOLATION
        self.store.set(f"/instances/{sid}", doc)  # line 47: VIOLATION
        self.store.set(f"/tables/{table}/config", doc)  # line 48: VIOLATION
        self.store.delete(f"/tables/{table}/segments/s")  # line 49: VIOLATION
