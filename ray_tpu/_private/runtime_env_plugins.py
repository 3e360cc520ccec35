"""runtime_env plugin API + the built-in pip plugin.

Reference: python/ray/_private/runtime_env/plugin.py (RuntimeEnvPlugin
base: priority, get_uris, create, modify_context, delete_uri) and
pip.py (hash-keyed virtualenv per pip spec). Scaled design:

  * a plugin OWNS one runtime_env key ("pip", ...); the node agent asks
    each registered plugin to (a) derive a deterministic URI from the
    env's config, (b) materialize that URI into a node-local cache dir
    once, and (c) mutate the worker spawn context (argv interpreter,
    env vars, cwd).
  * materialized URIs share the node's refcounted PackageCache — the
    same acquire/release/idle-GC lifecycle pkg:// extraction uses, so
    an idle venv is evicted exactly like an idle working_dir.
  * custom plugins load from RAY_TPU_RUNTIME_ENV_PLUGINS
    ("module:Class,module:Class" — reference RAY_RUNTIME_ENV_PLUGINS).

The pip plugin builds `python -m venv --system-site-packages` envs so
the framework and its deps stay importable, then pip-installs the
requested packages with any extra install options (tests use
--no-index --find-links for the zero-egress environment).
"""

from __future__ import annotations

import asyncio
import hashlib
import importlib
import json
import logging
import os
import shutil
import subprocess
import sys

logger = logging.getLogger(__name__)


class RuntimeEnvContext:
    """Mutable worker-spawn context handed to plugins (reference
    runtime_env/context.py RuntimeEnvContext)."""

    def __init__(self, env: dict, py_executable: str, cwd=None):
        self.env = env                    # process environment (mutable)
        self.py_executable = py_executable
        self.cwd = cwd


class RuntimeEnvPlugin:
    """One plugin per runtime_env key.

    Subclasses set `name` (the runtime_env dict key they own) and
    implement the three hooks. `create` runs in a thread off the agent
    loop and MUST be atomic: build into `dest + '.tmp'`, finish with
    os.replace — a crashed half-build must not poison the cache.
    """

    name: str = ""
    priority: int = 10  # lower runs first (reference plugin priority)

    def uri_for(self, config) -> str:
        """Deterministic URI for this config (content-addressed)."""
        raise NotImplementedError

    def create(self, uri: str, config, dest: str) -> None:
        """Materialize `uri` into directory `dest` (called once per node
        per URI; blocking, run off-loop)."""
        raise NotImplementedError

    def modify_context(self, uri: str, config, dest: str,
                       ctx: RuntimeEnvContext) -> None:
        """Apply the materialized env to the worker spawn context."""


def _config_digest(config) -> str:
    return hashlib.blake2b(
        json.dumps(config, sort_keys=True, default=str).encode(),
        digest_size=16,
    ).hexdigest()


def _relink_parent_sites(site_dir: str, extra: tuple = ()) -> None:
    """Write a .pth in `site_dir` re-linking the agent interpreter's
    site-packages (plus `extra` dirs): venvs built from a venv parent
    (this image: /opt/venv over /usr/local) would otherwise not see the
    parent's packages even with --system-site-packages; venv-installed
    packages still shadow them (the venv site dir sorts first)."""
    parent_sites = [p for p in sys.path
                    if p.rstrip(os.sep).endswith("site-packages")
                    and os.path.isdir(p)]
    with open(os.path.join(site_dir, "_parent_site.pth"), "w") as f:
        f.write("\n".join([*parent_sites, *extra]) + "\n")


def _venv_modify_context(dest: str, ctx: "RuntimeEnvContext") -> None:
    """Point the worker spawn at a materialized venv."""
    ctx.py_executable = os.path.join(dest, "bin", "python")
    ctx.env["VIRTUAL_ENV"] = dest
    ctx.env["PATH"] = (os.path.join(dest, "bin") + os.pathsep
                       + ctx.env.get("PATH", ""))


class PipPlugin(RuntimeEnvPlugin):
    """`runtime_env={"pip": [...]}` → per-hash virtualenv.

    Config forms (reference pip.py accepts the same two):
      {"pip": ["pkgA==1.0", "pkgB"]}
      {"pip": {"packages": [...], "install_options": ["--no-index", ...]}}

    The venv is keyed by (packages, install options, interpreter
    version) so two jobs with different pins never share an env.
    """

    name = "pip"
    priority = 5  # interpreter swap should precede cosmetic plugins

    @staticmethod
    def _normalize(config) -> tuple[list[str], list[str]]:
        if isinstance(config, (list, tuple)):
            pkgs, opts = list(config), []
        elif isinstance(config, dict):
            pkgs = list(config.get("packages") or [])
            opts = list(config.get("install_options") or [])
        else:
            raise ValueError(f"pip runtime_env must be a list or dict, "
                             f"got {type(config).__name__}")
        if not all(isinstance(p, str) for p in pkgs):
            raise ValueError(f"pip packages must be strings: {pkgs!r}")
        return pkgs, opts

    def uri_for(self, config) -> str:
        pkgs, opts = self._normalize(config)
        return "pip://" + _config_digest({
            "packages": sorted(pkgs), "options": opts,
            "py": sys.version_info[:2],
        })

    def create(self, uri: str, config, dest: str) -> None:
        pkgs, opts = self._normalize(config)
        tmp = dest + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        try:
            subprocess.run(
                [sys.executable, "-m", "venv", "--system-site-packages",
                 tmp],
                check=True, capture_output=True, timeout=300,
            )
            site_dir = os.path.join(
                tmp, "lib",
                f"python{sys.version_info[0]}.{sys.version_info[1]}",
                "site-packages")
            _relink_parent_sites(site_dir)
            if pkgs:
                py = os.path.join(tmp, "bin", "python")
                r = subprocess.run(
                    [py, "-m", "pip", "install", "--disable-pip-version-check",
                     *opts, *pkgs],
                    capture_output=True, text=True, timeout=600,
                )
                if r.returncode != 0:
                    raise RuntimeError(
                        f"pip install failed for {pkgs}: "
                        f"{r.stderr[-2000:]}"
                    )
            os.replace(tmp, dest)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    def modify_context(self, uri, config, dest, ctx) -> None:
        _venv_modify_context(dest, ctx)


class PyVersionPlugin(RuntimeEnvPlugin):
    """`runtime_env={"python_version": "3.11"}` — a full DIFFERENT
    interpreter per env: the conda-plugin equivalent (reference
    _private/runtime_env/conda.py:1, which materializes a whole conda
    env keyed by spec hash). This image is zero-egress with no
    conda/micromamba binary, so instead of solving an env spec the
    plugin discovers an installed CPython of the requested minor and
    builds a cached venv from it; the lifecycle — content-addressed
    URI, refcounted PackageCache materialization, idle GC, interpreter
    swap via modify_context — matches the conda plugin's.

    The venv gets a .pth re-linking the driver's site-packages so
    pure-python deps (incl. msgpack's fallback) import. Function
    payloads for such envs ship as SOURCE (pack_callable_source):
    bytecode is minor-specific."""

    name = "python_version"
    priority = 4  # interpreter swap precedes everything else

    _CANDIDATE_DIRS = ("/usr/bin", "/usr/local/bin", "/opt/bin")

    @classmethod
    def find_interpreter(cls, version: str) -> str | None:
        exe = shutil.which(f"python{version}")
        if exe:
            return exe
        for d in cls._CANDIDATE_DIRS:
            p = os.path.join(d, f"python{version}")
            if os.path.exists(p):
                return p
        return None

    @staticmethod
    def _normalize(config) -> str:
        v = str(config)
        parts = v.split(".")
        if len(parts) != 2 or not all(p.isdigit() for p in parts):
            raise ValueError(
                f'python_version must look like "3.11", got {config!r}')
        return v

    def uri_for(self, config) -> str:
        return "pyver://" + _config_digest(
            {"python": self._normalize(config)})

    def create(self, uri: str, config, dest: str) -> None:
        version = self._normalize(config)
        exe = self.find_interpreter(version)
        if exe is None:
            raise RuntimeError(
                f"no python{version} interpreter on this node "
                f"(searched PATH + {', '.join(self._CANDIDATE_DIRS)})")
        tmp = dest + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        try:
            # --without-pip: zero-egress image; deps come from the
            # driver site-packages re-link below
            subprocess.run([exe, "-m", "venv", "--without-pip", tmp],
                           check=True, capture_output=True, timeout=300)
            site_dir = os.path.join(tmp, "lib", f"python{version}",
                                    "site-packages")
            # the framework itself (workers run -m ray_tpu.core.worker_proc)
            import ray_tpu as _pkg

            _relink_parent_sites(site_dir, extra=(os.path.dirname(
                os.path.dirname(os.path.abspath(_pkg.__file__))),))
            os.replace(tmp, dest)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    def modify_context(self, uri, config, dest, ctx) -> None:
        _venv_modify_context(dest, ctx)


_BUILTIN = [PyVersionPlugin(), PipPlugin()]
_registry: dict[str, RuntimeEnvPlugin] | None = None


def registry() -> dict[str, RuntimeEnvPlugin]:
    global _registry
    if _registry is None:
        plugins = list(_BUILTIN)
        spec = os.environ.get("RAY_TPU_RUNTIME_ENV_PLUGINS", "")
        for item in filter(None, (s.strip() for s in spec.split(","))):
            try:
                mod, cls = item.split(":")
                plugins.append(getattr(importlib.import_module(mod), cls)())
            except Exception:  # noqa: BLE001 — a bad plugin spec must
                # not take the node agent down; the env just won't apply
                logger.exception("failed to load runtime_env plugin %r",
                                 item)
        _registry = {p.name: p for p in
                     sorted(plugins, key=lambda p: p.priority)}
    return _registry


def register_plugin(plugin: RuntimeEnvPlugin) -> None:
    """In-process registration (tests / embedded agents)."""
    global _registry
    reg = registry()
    reg[plugin.name] = plugin
    # re-sort: `priority` promises lower-runs-first even for plugins
    # registered after the registry was first built
    _registry = {p.name: p for p in
                 sorted(reg.values(), key=lambda p: p.priority)}


# in-flight creates keyed by (cache_root, uri): two concurrent spawns of
# the same env build the venv once, not twice
_creating: dict[tuple, asyncio.Future] = {}


async def apply_plugins(runtime_env: dict, ctx: RuntimeEnvContext,
                        cache) -> list[str]:
    """Agent-side: run every registered plugin whose key appears in the
    env. Returns the acquired URIs (caller releases them on worker
    death, same as pkg:// URIs)."""
    if "python_version" in runtime_env and "pip" in runtime_env:
        # PipPlugin builds its venv from the DRIVER interpreter; running
        # after PyVersionPlugin it would silently swap the interpreter
        # back — fail loudly instead of ignoring python_version
        raise RuntimeError(
            "runtime_env cannot combine 'python_version' with 'pip': "
            "pip venvs build from the driver interpreter")
    acquired: list[str] = []
    loop = asyncio.get_running_loop()
    try:
        for plugin in registry().values():
            config = runtime_env.get(plugin.name)
            if config is None:
                continue
            uri = plugin.uri_for(config)
            dest = cache.dir_for(uri)
            if not os.path.isdir(dest):
                key = (cache.root, uri)
                fut = _creating.get(key)
                if fut is None:
                    fut = loop.run_in_executor(
                        None, plugin.create, uri, config, dest)
                    _creating[key] = fut
                try:
                    await fut
                finally:
                    _creating.pop(key, None)
            cache.acquire(uri)
            acquired.append(uri)
            plugin.modify_context(uri, config, dest, ctx)
    except BaseException:
        # partial failure: the caller never sees `acquired`, so release
        # the refcounts here or earlier plugins' dirs are pinned forever
        for uri in acquired:
            cache.release(uri)
        raise
    return acquired
