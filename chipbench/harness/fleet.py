"""A configuration's fleet of servers, generated from the seed.

One directory per server (``<dir>/jvmNN/{server.log,app_jvmNN.log,
soap_io_jvmNN.log}``), as a WildFly fleet's log mount looks to the tailer
and the replay. The servers are generated in parallel by spawned worker
processes that import neither JAX nor the program, so set-up pays for the
slowest server and not for all of them.
"""

from __future__ import annotations

import multiprocessing
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .check import Expected
from .fixture import generate_server, records_params


def services_per_server(config: dict) -> List[int]:
    n = config["servers"]
    per = config["services_per_server"]
    counts = [int(per)] * n if isinstance(per, int) else [int(x) for x in per]
    if len(counts) != n:
        raise ValueError(f"services_per_server lists {len(counts)} servers, not {n}")
    return counts


def server_name(i: int) -> str:
    return f"jvm{i:02d}"


def _one(job) -> tuple:
    server, index, names, n_tx, tx_per_bucket, seed, records, out_dir = job
    logs = generate_server(server, names, n_tx, tx_per_bucket, seed, records, index)
    logs.write(os.path.join(out_dir, server))
    files = {name: (ends, rel) for name, (_blob, ends, rel) in logs.files.items()}
    recs = (np.asarray(logs.rec_service), np.asarray(logs.rec_route, np.int8),
            np.asarray(logs.rec_end_ms, np.int64), np.asarray(logs.rec_elapsed, np.int32),
            np.asarray(logs.rec_file), np.asarray(logs.rec_line, np.int64),
            np.asarray(logs.rec_acct_file), np.asarray(logs.rec_acct_line, np.int64))
    return server, files, recs


class Fleet:
    """The generated logs: per file (path) its line end offsets and release
    log times, and every record with the file and line that complete it."""

    def __init__(self, log_dir: str, results: Sequence[tuple]):
        self.log_dir = log_dir
        self.files: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
        server, service, route, end_ms, elapsed, path, line = [], [], [], [], [], [], []
        acct_path, acct_line = [], []
        for name, files, (svc, rt, end, el, fname, ln, afile, aline) in results:
            for fn, arrays in files.items():
                self.files[os.path.join(log_dir, name, fn)] = arrays
            server.extend([name] * len(svc))
            service.extend(svc.tolist())
            route.append(rt)
            end_ms.append(end)
            elapsed.append(el)
            path.extend(os.path.join(log_dir, name, f) for f in fname.tolist())
            line.append(ln)
            acct_path.extend(os.path.join(log_dir, name, f) if f else "" for f in afile.tolist())
            acct_line.append(aline)
        self.rec_server = server
        self.rec_service = service
        self.rec_route = np.concatenate(route)
        self.rec_end_ms = np.concatenate(end_ms)
        self.rec_elapsed = np.concatenate(elapsed)
        self.rec_path = path
        self.rec_line = np.concatenate(line)
        self.rec_acct_path = acct_path
        self.rec_acct_line = np.concatenate(acct_line)

    def records_within(self, lines_fed: Dict[str, int]) -> int:
        """How many records the fed prefix completes (engine-bound and DB)."""
        if not hasattr(self, "_lines_by_path"):
            by: Dict[str, list] = {}
            for p, ln in zip(self.rec_path, self.rec_line.tolist()):
                by.setdefault(p, []).append(ln)
            self._lines_by_path = {p: np.sort(np.asarray(v)) for p, v in by.items()}
        return sum(int(np.searchsorted(lines, lines_fed.get(p, 0), side="left"))
                   for p, lines in self._lines_by_path.items())

    def expected(self, lines_fed: Dict[str, int], arrival: Dict[str, np.ndarray]) -> Expected:
        """The records whose completing line lies in the fed prefix of its
        file, each with its place in the order the parser hands records on.

        ``arrival[path][line]`` is the feed's sequence number of the line
        (the replay's chunk). A SOAP/EJB
        record leaves the parser once both its server.log exit line and its
        soap_io account line are read; any other engine-bound record is
        parked, as the parser parks a record with no SOAP account, and leaves
        at a later sweep: its place is where it was parked."""
        keep = np.fromiter((ln < lines_fed.get(p, 0) for p, ln in
                            zip(self.rec_path, self.rec_line.tolist())), bool, len(self.rec_path))
        idx = np.nonzero(keep)[0].tolist()
        pid = {p: i for i, p in enumerate(sorted(self.files))}
        place = []
        parked = []
        for i in idx:
            p, ln = self.rec_path[i], int(self.rec_line[i])
            key = (int(arrival[p][ln]), pid[p], ln)
            ap = self.rec_acct_path[i]
            if ap:
                al = int(self.rec_acct_line[i])
                key = max(key, (int(arrival[ap][al]), pid[ap], al))
            place.append(key)
            parked.append(not ap)
        rank = np.empty(len(idx), np.int64)
        rank[sorted(range(len(idx)), key=place.__getitem__)] = np.arange(len(idx))
        return Expected([self.rec_server[i] for i in idx], [self.rec_service[i] for i in idx],
                        self.rec_route[keep], self.rec_end_ms[keep], self.rec_elapsed[keep],
                        rank, np.asarray(parked, bool), np.asarray([k[0] for k in place], np.int64))

    def engine_keys(self) -> List[tuple]:
        keys = {(s, v) for s, v, r in zip(self.rec_server, self.rec_service,
                                           self.rec_route.tolist()) if r == 0}
        return sorted(keys)


def generate_fleet(config: dict, n_tx_per_server: int, seed: int, log_dir: str,
                   workers: int, records: Optional[dict] = None) -> Fleet:
    """Write the fleet's logs under ``log_dir``, each server's transactions
    drawn as the traffic's ``records`` section says; servers are seeded
    ``seed + i`` as chip_smoke.py's ``write_fleet`` seeds them."""
    records_params(records)  # an unknown parameter fails here, not in a worker
    counts = services_per_server(config)
    names = [f"svc{i:03d}" for i in range(max(counts))]
    jobs = [(server_name(i), i, tuple(names[:c]), n_tx_per_server, float(config["tx_per_bucket"]),
             seed + i, records, log_dir) for i, c in enumerate(counts)]
    if workers <= 1:
        results = [_one(j) for j in jobs]
    else:
        with multiprocessing.get_context("spawn").Pool(workers) as pool:
            results = pool.map(_one, jobs, chunksize=1)
    return Fleet(log_dir, results)
