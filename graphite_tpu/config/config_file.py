"""carbon_sim.cfg-compatible hierarchical INI configuration.

Reference behavior being matched (not translated):
 - hierarchical sections `[a/b/c]` (`common/config/config.hpp`,
   grammar `common/config/config_file_grammar.hpp:7-11`);
 - values are quoted strings, integers, floats, or true/false
   (`carbon_sim.cfg:7-8`);
 - `#` starts a comment, including trailing comments after values
   (`carbon_sim.cfg` throughout, e.g. `:143`);
 - typed getters `getInt/getBool/getString/getFloat` keyed by full path
   `"section/sub/key"` (`common/config/config_file.hpp:20-42`);
 - CLI overrides `--section/sub/key=value` and `-c <file>` merged on top
   (`common/misc/handle_args.cc:45-58`).

This is a fresh pure-Python implementation (the reference uses boost-spirit);
only the observable config surface is reproduced.
"""

from __future__ import annotations

import re
from typing import Any, Iterable


class ConfigError(KeyError):
    pass


_SECTION_RE = re.compile(r"^\[([A-Za-z0-9_/\-]+)\]\s*$")
_KEY_RE = re.compile(r"^([A-Za-z0-9_\-]+)\s*=\s*(.*)$")


def _strip_comment(line: str) -> str:
    """Remove a trailing # comment, respecting double-quoted strings."""
    out = []
    in_quote = False
    for ch in line:
        if ch == '"':
            in_quote = not in_quote
        elif ch == "#" and not in_quote:
            break
        out.append(ch)
    return "".join(out)


class ConfigFile:
    """Flat map of "section/sub/key" -> raw string value, with typed getters."""

    def __init__(self) -> None:
        self._values: dict[str, str] = {}

    # --- loading ---------------------------------------------------------

    @classmethod
    def from_file(cls, path: str) -> "ConfigFile":
        cfg = cls()
        with open(path, "r") as f:
            cfg.load_string(f.read())
        return cfg

    @classmethod
    def from_string(cls, text: str) -> "ConfigFile":
        cfg = cls()
        cfg.load_string(text)
        return cfg

    def load_string(self, text: str) -> None:
        section = ""
        for lineno, raw in enumerate(text.splitlines(), start=1):
            line = _strip_comment(raw).strip()
            if not line:
                continue
            m = _SECTION_RE.match(line)
            if m:
                section = m.group(1).strip("/")
                # register the section even if empty (e.g. [core] at
                # carbon_sim.cfg:178 has no keys of its own)
                continue
            m = _KEY_RE.match(line)
            if m is None:
                raise ConfigError(f"config parse error at line {lineno}: {raw!r}")
            key, value = m.group(1), m.group(2).strip()
            full = f"{section}/{key}" if section else key
            self._values[full] = value

    def merge(self, other: "ConfigFile") -> None:
        """Later files / overrides win (handle_args.cc merge-on-top)."""
        self._values.update(other._values)

    def set(self, path: str, value: Any) -> None:
        if isinstance(value, bool):
            value = "true" if value else "false"
        self._values[path.strip("/")] = str(value)

    # --- typed getters ---------------------------------------------------

    _MISSING = object()

    def _raw(self, path: str, default: Any = _MISSING) -> str:
        path = path.strip("/")
        if path in self._values:
            return self._values[path]
        if default is not ConfigFile._MISSING:
            return default
        raise ConfigError(f"missing config key: {path}")

    def has(self, path: str) -> bool:
        return path.strip("/") in self._values

    def has_section(self, section: str) -> bool:
        """Whether any key lies under `[section]` or a sub-section."""
        prefix = section.strip("/") + "/"
        return any(k.startswith(prefix) for k in self._values)

    def get_string(self, path: str, default: Any = _MISSING) -> str:
        v = self._raw(path, default)
        if not isinstance(v, str):
            return v
        v = v.strip()
        if len(v) >= 2 and v[0] == '"' and v[-1] == '"':
            v = v[1:-1]
        return v

    def get_int(self, path: str, default: Any = _MISSING) -> int:
        v = self._raw(path, default)
        if not isinstance(v, str):
            return v
        try:
            return int(v, 0)
        except ValueError:
            # the reference tolerates float-formatted ints in int contexts
            try:
                f = float(v)
            except ValueError:
                raise ConfigError(f"config key {path} = {v!r} is not an int")
            if f != int(f):
                raise ConfigError(f"config key {path} = {v!r} is not an int")
            return int(f)

    def get_float(self, path: str, default: Any = _MISSING) -> float:
        v = self._raw(path, default)
        if not isinstance(v, str):
            return v
        return float(v)

    def get_bool(self, path: str, default: Any = _MISSING) -> bool:
        v = self._raw(path, default)
        if not isinstance(v, str):
            return v
        lv = v.strip().lower()
        if lv in ("true", "1"):
            return True
        if lv in ("false", "0"):
            return False
        raise ConfigError(f"config key {path} = {v!r} is not a bool")

    # --- introspection ---------------------------------------------------

    def keys(self) -> Iterable[str]:
        return self._values.keys()

    def section(self, prefix: str) -> dict[str, str]:
        """All keys directly under `prefix` (used for [process_map])."""
        prefix = prefix.strip("/") + "/"
        out = {}
        for k, v in self._values.items():
            if k.startswith(prefix) and "/" not in k[len(prefix):]:
                out[k[len(prefix):]] = v
        return out

    def as_dict(self) -> dict[str, str]:
        return dict(self._values)


def parse_override_args(argv: list[str]) -> tuple[list[str], ConfigFile, str | None]:
    """Parse `-c <file>` and `--section/key=value` overrides.

    Mirrors `common/misc/handle_args.cc:45-58`: returns (remaining argv,
    override ConfigFile, config file path or None).
    """
    overrides = ConfigFile()
    cfg_path: str | None = None
    rest: list[str] = []
    i = 0
    while i < len(argv):
        arg = argv[i]
        if arg == "-c":
            if i + 1 >= len(argv):
                raise ConfigError("-c requires a file argument")
            cfg_path = argv[i + 1]
            i += 2
            continue
        if arg.startswith("-c="):
            cfg_path = arg[len("-c="):]
            i += 1
            continue
        if arg.startswith("--") and "=" in arg:
            path, _, value = arg[2:].partition("=")
            overrides.set(path, value)
            i += 1
            continue
        rest.append(arg)
        i += 1
    return rest, overrides, cfg_path


def load_config(path: str | None, argv: list[str] | None = None) -> ConfigFile:
    """Load a config file then apply CLI overrides on top."""
    argv = argv or []
    rest, overrides, cli_path = parse_override_args(argv)
    cfg_path = cli_path or path
    if cfg_path is None:
        raise ConfigError("no config file given")
    cfg = ConfigFile.from_file(cfg_path)
    cfg.merge(overrides)
    return cfg
