"""Seeded rows at the schema of KDD Cup 1999's connection records.

The 41 features of the KDD Cup 1999 task description, in four groups:
basic connection features (9), content features (13), time-based traffic
features (9) and host-based traffic features (10).  Three are symbolic:
``protocol_type`` (3 symbols), ``service`` (70) and ``flag`` (11), drawn
here Zipf-skewed over their full symbol lists; a symbolic value is carried
as its index in the published list.  Byte counts are heavy-tailed, the
connection counts lie in 0..511, and the rates in [0, 1] at two decimals.

The label (1 = attack, about 80% of rows as in the 10% training file)
depends on a group of services and a group of flags that are not runs of
consecutive codes, so no threshold on the codes makes the split a set of
categories makes; byte counts and traffic features follow the label with
noise, and some labels are flipped, so trees keep splitting down to their
last level.

Columns come in global feature-id order: the network operator's 28
(basic, time-based, host-based) and then the host-security vendor's 13
content features; :data:`COLUMNS` names them.  Plain NumPy, a function of
the seed alone.
"""
from __future__ import annotations

import numpy as np

PROTOCOLS = ("icmp", "tcp", "udp")
SERVICES = (
    "IRC", "X11", "Z39_50", "aol", "auth", "bgp", "courier", "csnet_ns",
    "ctf", "daytime", "discard", "domain", "domain_u", "echo", "eco_i",
    "ecr_i", "efs", "exec", "finger", "ftp", "ftp_data", "gopher", "harvest",
    "hostnames", "http", "http_2784", "http_443", "http_8001", "imap4",
    "iso_tsap", "klogin", "kshell", "ldap", "link", "login", "mtp", "name",
    "netbios_dgm", "netbios_ns", "netbios_ssn", "netstat", "nnsp", "nntp",
    "ntp_u", "other", "pm_dump", "pop_2", "pop_3", "printer", "private",
    "red_i", "remote_job", "rje", "shell", "smtp", "sql_net", "ssh",
    "sunrpc", "supdup", "systat", "telnet", "tftp_u", "tim_i", "time",
    "urh_i", "urp_i", "uucp", "uucp_path", "vmnet", "whois")
FLAGS = ("OTH", "REJ", "RSTO", "RSTOS0", "RSTR", "S0", "S1", "S2", "S3",
         "SF", "SH")
SYMBOLS = {"protocol_type": PROTOCOLS, "service": SERVICES, "flag": FLAGS}

BASIC = ("duration", "protocol_type", "service", "flag", "src_bytes",
         "dst_bytes", "land", "wrong_fragment", "urgent")
CONTENT = ("hot", "num_failed_logins", "logged_in", "num_compromised",
           "root_shell", "su_attempted", "num_root", "num_file_creations",
           "num_shells", "num_access_files", "num_outbound_cmds",
           "is_host_login", "is_guest_login")
TIME = ("count", "srv_count", "serror_rate", "srv_serror_rate",
        "rerror_rate", "srv_rerror_rate", "same_srv_rate", "diff_srv_rate",
        "srv_diff_host_rate")
HOST = ("dst_host_count", "dst_host_srv_count", "dst_host_same_srv_rate",
        "dst_host_diff_srv_rate", "dst_host_same_src_port_rate",
        "dst_host_srv_diff_host_rate", "dst_host_serror_rate",
        "dst_host_srv_serror_rate", "dst_host_rerror_rate",
        "dst_host_srv_rerror_rate")
COLUMNS = BASIC + TIME + HOST + CONTENT          # global feature-id order
CATEGORICAL = tuple(COLUMNS.index(c) for c in SYMBOLS)

# services and flags that raise the odds of an attack: neither group is a
# run of consecutive codes
ATTACK_SERVICES = ("ecr_i", "private", "eco_i", "other", "finger", "telnet",
                   "ftp", "domain_u", "X11", "imap4", "sunrpc", "urp_i")
ATTACK_FLAGS = ("S0", "REJ", "RSTO", "RSTOS0", "SH", "OTH")
LABEL_NOISE = 0.04


# the symbols by popularity, most common first (the rest of each list
# follows in its own order): what the Zipf ranks map to
POPULAR = {
    "protocol_type": ("icmp", "tcp", "udp"),
    "service": ("ecr_i", "private", "http", "smtp", "other", "domain_u",
                "ftp_data", "eco_i", "ftp", "finger", "urp_i", "telnet",
                "ntp_u", "auth", "pop_3", "time", "domain", "imap4",
                "sunrpc", "X11"),
    "flag": ("SF", "S0", "REJ", "RSTR", "RSTO", "SH", "S1", "S2", "RSTOS0",
             "S3", "OTH"),
}


def _zipf(rng, name: str, n: int, s: float = 1.1) -> np.ndarray:
    """``n`` draws of symbol indices of list ``name``, Zipf-skewed over its
    popularity ranking, so every symbol has some rows and the common ones
    are not the first codes."""
    symbols = SYMBOLS[name]
    top = [symbols.index(v) for v in POPULAR[name]]
    ranking = np.array(top + [i for i in range(len(symbols))
                              if i not in top])
    p = 1.0 / np.arange(1, len(symbols) + 1) ** s
    return ranking[rng.choice(len(symbols), size=n, p=p / p.sum())]


def _heavy(rng, n: int, scale: float, zero: float) -> np.ndarray:
    """Non-negative integer byte counts: a share ``zero`` of zeros, the rest
    log-normal around ``scale``."""
    v = np.floor(np.exp(rng.normal(np.log(scale), 1.5, size=n)))
    return np.where(rng.random(n) < zero, 0.0, v)


def _rate(rng, n: int, mean: np.ndarray) -> np.ndarray:
    return np.round(np.clip(rng.beta(0.6, 0.6, size=n) * 0.5 + mean
                            - 0.25, 0.0, 1.0), 2)


def make_table(n: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(x (n, 41) float64 in :data:`COLUMNS` order, y (n,) int64 labels)."""
    rng = np.random.default_rng([seed, 99])
    proto = _zipf(rng, "protocol_type", n)
    service = _zipf(rng, "service", n)
    flag = _zipf(rng, "flag", n)
    svc_bad = np.isin(service, [SERVICES.index(s) for s in ATTACK_SERVICES])
    flag_bad = np.isin(flag, [FLAGS.index(f) for f in ATTACK_FLAGS])
    # a latent attack score: the service and flag groups dominate, and the
    # noise keeps both classes in every group
    z = (2.2 * svc_bad + 1.6 * flag_bad + 0.8 * (proto == 0)
         + rng.normal(0.0, 1.0, size=n))
    attack = z > 0.85
    cols: dict[str, np.ndarray] = {
        "duration": np.where(rng.random(n) < 0.92, 0.0,
                             np.floor(rng.exponential(300.0, size=n))),
        "protocol_type": proto.astype(np.float64),
        "service": service.astype(np.float64),
        "flag": flag.astype(np.float64),
        "src_bytes": _heavy(rng, n, np.where(attack, 520.0, 250.0), 0.2),
        "dst_bytes": _heavy(rng, n, np.where(attack, 20.0, 2000.0),
                            0.5),
        "land": (rng.random(n) < 0.0005).astype(np.float64),
        "wrong_fragment": np.where(rng.random(n) < 0.003,
                                   rng.integers(1, 4, size=n), 0),
        "urgent": np.where(rng.random(n) < 0.0005, 1, 0),
    }
    busy = np.where(attack, 400.0, 20.0)
    cols["count"] = np.clip(np.floor(rng.gamma(1.5, busy / 1.5)), 0, 511)
    cols["srv_count"] = np.clip(np.floor(cols["count"]
                                         * rng.uniform(0.2, 1.0, n)), 0, 511)
    s0 = (flag == FLAGS.index("S0")).astype(np.float64)
    rej = (flag == FLAGS.index("REJ")).astype(np.float64)
    cols["serror_rate"] = _rate(rng, n, 0.8 * s0)
    cols["srv_serror_rate"] = _rate(rng, n, 0.8 * s0)
    cols["rerror_rate"] = _rate(rng, n, 0.8 * rej)
    cols["srv_rerror_rate"] = _rate(rng, n, 0.8 * rej)
    cols["same_srv_rate"] = _rate(rng, n, np.where(attack, 0.4, 0.9))
    cols["diff_srv_rate"] = _rate(rng, n, np.where(attack, 0.3, 0.05))
    cols["srv_diff_host_rate"] = _rate(rng, n, 0.1)
    cols["dst_host_count"] = np.clip(np.floor(rng.gamma(2.0, 110.0, n)),
                                     0, 255)
    cols["dst_host_srv_count"] = np.clip(
        np.floor(rng.gamma(2.0, np.where(attack, 60.0, 100.0))), 0, 255)
    for c in HOST[2:]:
        boost = (0.6 * s0 if "serror" in c else 0.6 * rej if "rerror" in c
                 else np.where(attack, 0.3, 0.6))
        cols[c] = _rate(rng, n, boost)
    logged = (~attack & (rng.random(n) < 0.7)) | (rng.random(n) < 0.05)
    for c in CONTENT:
        if c == "logged_in":
            cols[c] = logged.astype(np.float64)
        elif c in ("is_host_login", "is_guest_login", "root_shell",
                   "su_attempted"):
            cols[c] = (rng.random(n) < 0.002).astype(np.float64)
        elif c == "num_outbound_cmds":
            cols[c] = np.zeros(n)
        else:
            cols[c] = np.where(rng.random(n) < 0.03,
                               np.floor(rng.exponential(3.0, size=n)), 0.0)
    x = np.stack([np.asarray(cols[c], np.float64) for c in COLUMNS], axis=1)
    y = attack ^ (rng.random(n) < LABEL_NOISE)
    return x, y.astype(np.int64)


def with_unseen(x: np.ndarray, share: float, seed: int) -> np.ndarray:
    """A copy of rows ``x`` in which a ``share`` of each categorical
    column's values are symbols outside its published list (index past its
    end), as a held-out set sees categories the fit never did."""
    rng = np.random.default_rng([seed, 98])
    x = x.copy()
    for j, name in zip(CATEGORICAL, SYMBOLS):
        hit = rng.random(len(x)) < share
        x[hit, j] = len(SYMBOLS[name]) + rng.integers(0, 5, size=hit.sum())
    return x


def party_columns(widths: list[int]) -> list[np.ndarray]:
    """Global feature ids per party: contiguous, in party order."""
    edges = np.cumsum([0] + list(widths))
    return [np.arange(lo, hi) for lo, hi in zip(edges[:-1], edges[1:])]
