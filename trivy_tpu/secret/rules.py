"""Builtin secret rules — capability parity with the reference's 86-rule
set (pkg/fanal/secret/builtin-rules.go; rule IDs/titles/severities/keyword
gates match so findings diff cleanly). The token formats are the public,
vendor-documented shapes. Patterns are authored table-driven: most rules
are either a bare prefixed-token regex or a "key-assignment" shape
(`<service-ish key> <assign op> "<secret>"`).

Global allow rules mirror builtin-allow-rules.go (test/example/vendor
paths etc.)."""

from __future__ import annotations

import functools
import re
from dataclasses import dataclass, field
from typing import Optional

from .anchor import Anchor, derive

# shared grammar fragments
QUOTE = r"""["']?"""
CONNECT = r"\s*(:|=>|=)?\s*"
START = r"(^|\s+)"
END = r"[.,]?(\s+|$)"
UUID = r"[0-9A-F]{8}-[0-9A-F]{4}-[0-9A-F]{4}-[0-9A-F]{4}-[0-9A-F]{12}"


@dataclass
class AllowRule:
    id: str
    description: str = ""
    regex: Optional[re.Pattern] = None
    path: Optional[re.Pattern] = None


@dataclass
class Rule:
    id: str
    category: str
    title: str
    severity: str
    regex: re.Pattern
    keywords: list
    secret_group: str = ""
    # duplicate-name aliases of secret_group (Go regexps may bind one
    # name twice; each occurrence yields its own finding)
    secret_aliases: tuple = ()
    path: Optional[re.Pattern] = None
    allow_rules: list = field(default_factory=list)
    exclude_regexes: list = field(default_factory=list)

    @functools.cached_property
    def anchor(self) -> Optional[Anchor]:
        """Where a match can start (secret/anchor.py), derived from the
        compiled pattern on first use; None keeps the whole-text scan."""
        return derive(self.regex)

    def match_path(self, path: str) -> bool:
        return self.path is None or bool(self.path.search(path))

    def allow_path(self, path: str) -> bool:
        return any(a.path and a.path.search(path) for a in self.allow_rules)

    def allow_match(self, match: str) -> bool:
        return any(a.regex and a.regex.search(match)
                   for a in self.allow_rules)

    def match_keywords(self, lower_content: bytes) -> bool:
        if not self.keywords:
            return True
        return any(k.lower().encode() in lower_content
                   for k in self.keywords)


GLOBAL_ALLOW_RULES = [
    AllowRule("tests", "Avoid test files and paths",
              path=re.compile(r"(^test|\/test|-test|_test|\.test)")),
    AllowRule("examples", "Avoid example files and paths",
              path=re.compile(r"example"),
              regex=re.compile(r"(?i)example")),
    AllowRule("vendor", "Vendor dirs", path=re.compile(r"\/vendor\/")),
    AllowRule("usr-dirs", "System dirs",
              path=re.compile(r"^usr\/(?:share|include|lib)\/")),
    AllowRule("locale-dir", "Locales directory",
              path=re.compile(r"\/locales?\/")),
    AllowRule("markdown", "Markdown files", path=re.compile(r"\.md$")),
    AllowRule("node.js", "Node container images",
              path=re.compile(r"^opt\/yarn-v[\d.]+\/")),
    AllowRule("golang", "Go container images",
              path=re.compile(r"^usr\/local\/go\/")),
    AllowRule("python", "Python container images",
              path=re.compile(r"^usr\/local\/lib\/python[\d.]+\/")),
    AllowRule("rubygems", "Ruby container images",
              path=re.compile(r"^usr\/lib\/gems\/")),
    AllowRule("wordpress", "Wordpress container images",
              path=re.compile(r"^usr\/src\/wordpress\/")),
    AllowRule("anaconda-log", "Anaconda CI logs",
              path=re.compile(r"^var\/log\/anaconda\/")),
]


def _assign(key_prefix: str, secret_pat: str) -> str:
    """Key-assignment rule shape: `<key>... = "<secret>"`."""
    return (rf""" (?i)(?P<key>{key_prefix}[a-z0-9_ .\-,]{{0,25}})"""
            rf"""(=|>|:=|\|\|:|<=|=>|:).{{0,5}}['\"]"""
            rf"""(?P<secret>{secret_pat})['\"]""")


def _quoted(pat: str) -> str:
    return rf"""['\"]{pat}['\"]"""


# (id, category, title, severity, regex, keywords, secret_group)
_TABLE = [
    ("aws-access-key-id", "AWS", "AWS Access Key ID", "CRITICAL",
     QUOTE + r"(?P<secret>(A3T[A-Z0-9]|AKIA|AGPA|AIDA|AROA|AIPA|ANPA|ANVA|"
     r"ASIA)[A-Z0-9]{16})" + QUOTE + END,
     ["AKIA", "AGPA", "AIDA", "AROA", "AIPA", "ANPA", "ANVA", "ASIA"],
     "secret"),
    ("aws-secret-access-key", "AWS", "AWS Secret Access Key", "CRITICAL",
     r"(?i)" + START + QUOTE + r"aws_?" + r"(sec(ret)?)?_?(access)?_?key" +
     QUOTE + CONNECT + QUOTE + r"(?P<secret>[A-Za-z0-9\/\+=]{40})" + QUOTE +
     END,
     ["key"], "secret"),
    ("github-pat", "GitHub", "GitHub Personal Access Token", "CRITICAL",
     r"ghp_[0-9a-zA-Z]{36}", ["ghp_"], ""),
    ("github-oauth", "GitHub", "GitHub OAuth Access Token", "CRITICAL",
     r"gho_[0-9a-zA-Z]{36}", ["gho_"], ""),
    ("github-app-token", "GitHub", "GitHub App Token", "CRITICAL",
     r"(ghu|ghs)_[0-9a-zA-Z]{36}", ["ghu_", "ghs_"], ""),
    ("github-refresh-token", "GitHub", "GitHub Refresh Token", "CRITICAL",
     r"ghr_[0-9a-zA-Z]{76}", ["ghr_"], ""),
    ("github-fine-grained-pat", "GitHub",
     "GitHub Fine-grained personal access tokens", "CRITICAL",
     r"github_pat_[0-9a-zA-Z_]{82}", ["github_pat_"], ""),
    ("gitlab-pat", "GitLab", "GitLab Personal Access Token", "CRITICAL",
     r"glpat-[0-9a-zA-Z\-\_]{20}", ["glpat-"], ""),
    ("hugging-face-access-token", "HuggingFace", "Hugging Face Access Token",
     "CRITICAL", r"hf_[A-Za-z0-9]{34,40}", ["hf_"], ""),
    ("private-key", "AsymmetricPrivateKey", "Asymmetric Private Key", "HIGH",
     r"(?i)-----\s*?BEGIN[ A-Z0-9_-]*?PRIVATE KEY( BLOCK)?\s*?-----[\s]*?"
     r"(?P<secret>[\sA-Za-z0-9=+/\\\r\n]+)[\s]*?-----\s*?END[ A-Z0-9_-]*? ?"
     r"PRIVATE KEY( BLOCK)?\s*?-----", ["-----"], "secret"),
    ("shopify-token", "Shopify", "Shopify token", "HIGH",
     r"shp(ss|at|ca|pa)_[a-fA-F0-9]{32}",
     ["shpss_", "shpat_", "shpca_", "shppa_"], ""),
    ("slack-access-token", "Slack", "Slack token", "HIGH",
     r"xox[baprs]-([0-9a-zA-Z]{10,48})?",
     ["xoxb-", "xoxa-", "xoxp-", "xoxr-", "xoxs-"], ""),
    ("stripe-publishable-token", "Stripe", "Stripe Publishable Key", "LOW",
     r"(?i)pk_(test|live)_[0-9a-z]{10,32}", ["pk_test_", "pk_live_"], ""),
    ("stripe-secret-token", "Stripe", "Stripe Secret Key", "CRITICAL",
     r"(?i)sk_(test|live)_[0-9a-z]{10,32}", ["sk_test_", "sk_live_"], ""),
    ("pypi-upload-token", "PyPI", "PyPI upload token", "HIGH",
     r"pypi-AgEIcHlwaS5vcmc[A-Za-z0-9\-_]{50,1000}",
     ["pypi-AgEIcHlwaS5vcmc"], ""),
    ("gcp-service-account", "Google", "Google (GCP) Service-account",
     "CRITICAL", r"\"type\": \"service_account\"",
     ['"type": "service_account"'], ""),
    ("heroku-api-key", "Heroku", "Heroku API Key", "HIGH",
     _assign("heroku", UUID), ["heroku"], "secret"),
    ("slack-web-hook", "Slack", "Slack Webhook", "MEDIUM",
     r"https:\/\/hooks.slack.com\/services\/T[a-zA-Z0-9_]{8}\/"
     r"B[a-zA-Z0-9_]{8,12}\/[a-zA-Z0-9_]{24}", ["hooks.slack.com"], ""),
    ("twilio-api-key", "Twilio", "Twilio API Key", "MEDIUM",
     r"SK[0-9a-fA-F]{32}", ["SK"], ""),
    ("age-secret-key", "Age", "Age secret key", "MEDIUM",
     r"AGE-SECRET-KEY-1[QPZRY9X8GF2TVDW0S3JN54KHCE6MUA7L]{58}",
     ["AGE-SECRET-KEY-1"], ""),
    ("facebook-token", "Facebook", "Facebook token", "LOW",
     _assign("facebook", r"[a-f0-9]{32}"), ["facebook"], "secret"),
    ("twitter-token", "Twitter", "Twitter token", "LOW",
     _assign("twitter", r"[a-f0-9]{35,44}"), ["twitter"], "secret"),
    ("adobe-client-id", "Adobe", "Adobe Client ID (Oauth Web)", "LOW",
     _assign("adobe", r"[a-f0-9]{32}"), ["adobe"], "secret"),
    ("adobe-client-secret", "Adobe", "Adobe Client Secret", "LOW",
     r"(p8e-)(?i)[a-z0-9]{32}", ["p8e-"], ""),
    ("alibaba-access-key-id", "Alibaba", "Alibaba AccessKey ID", "HIGH",
     r"([^0-9A-Za-z]|^)(?P<secret>(LTAI)(?i)[a-z0-9]{20})"
     r"([^0-9A-Za-z]|$)",
     ["LTAI"], "secret"),
    ("alibaba-secret-key", "Alibaba", "Alibaba Secret Key", "HIGH",
     _assign("alibaba", r"[a-z0-9]{30}"), ["alibaba"], "secret"),
    ("asana-client-id", "Asana", "Asana Client ID", "MEDIUM",
     _assign("asana", r"[0-9]{16}"), ["asana"], "secret"),
    ("asana-client-secret", "Asana", "Asana Client Secret", "MEDIUM",
     _assign("asana", r"[a-z0-9]{32}"), ["asana"], "secret"),
    ("atlassian-api-token", "Atlassian", "Atlassian API token", "HIGH",
     _assign("atlassian", r"[a-z0-9]{24}"), ["atlassian"], "secret"),
    ("bitbucket-client-id", "Bitbucket", "Bitbucket client ID", "HIGH",
     _assign("bitbucket", r"[a-z0-9]{32}"), ["bitbucket"], "secret"),
    ("bitbucket-client-secret", "Bitbucket", "Bitbucket client secret",
     "HIGH", _assign("bitbucket", r"[a-z0-9_\-]{64}"), ["bitbucket"],
     "secret"),
    ("beamer-api-token", "Beamer", "Beamer API token", "LOW",
     _assign("beamer", r"b_[a-z0-9=_\-]{44}"), ["beamer"], "secret"),
    ("clojars-api-token", "Clojars", "Clojars API token", "MEDIUM",
     r"(?i)(CLOJARS_)[a-z0-9]{60}", ["clojars"], ""),
    ("contentful-delivery-api-token", "Contentful",
     "Contentful delivery API token", "LOW",
     _assign("contentful", r"[a-z0-9\-=_]{43}"), ["contentful"], "secret"),
    ("databricks-api-token", "Databricks", "Databricks API token", "MEDIUM",
     r"dapi[a-h0-9]{32}", ["dapi"], ""),
    ("discord-api-token", "Discord", "Discord API key", "MEDIUM",
     _assign("discord", r"[a-h0-9]{64}"), ["discord"], "secret"),
    ("discord-client-id", "Discord", "Discord client ID", "MEDIUM",
     _assign("discord", r"[0-9]{18}"), ["discord"], "secret"),
    ("discord-client-secret", "Discord", "Discord client secret", "MEDIUM",
     _assign("discord", r"[a-z0-9=_\-]{32}"), ["discord"], "secret"),
    ("doppler-api-token", "Doppler", "Doppler API token", "MEDIUM",
     _quoted(r"(dp\.pt\.)(?i)[a-z0-9]{43}"), ["doppler"], ""),
    ("dropbox-api-secret", "Dropbox", "Dropbox API secret/key", "HIGH",
     _assign("dropbox", r"[a-z0-9]{15}"), ["dropbox"], "secret"),
    ("dropbox-short-lived-api-token", "Dropbox",
     "Dropbox short lived API token", "HIGH",
     _assign("dropbox", r"sl\.[a-z0-9\-=_]{135}"), ["dropbox"], "secret"),
    ("dropbox-long-lived-api-token", "Dropbox",
     "Dropbox long lived API token", "HIGH",
     _assign("dropbox", r"[a-z0-9]{11}(AAAAAAAAAA)[a-z0-9\-_=]{43}"),
     ["dropbox"], "secret"),
    ("duffel-api-token", "Duffel", "Duffel API token", "LOW",
     _quoted(r"duffel_(test|live)_(?i)[a-z0-9_-]{43}"), ["duffel"], ""),
    ("dynatrace-api-token", "Dynatrace", "Dynatrace API token", "MEDIUM",
     _quoted(r"dt0c01\.(?i)[a-z0-9]{24}\.[a-z0-9]{64}"), ["dynatrace"], ""),
    ("easypost-api-token", "EasyPost", "EasyPost API token", "LOW",
     _quoted(r"EZ[AT]K(?i)[a-z0-9]{54}"), ["EZAK", "EZTK"], ""),
    ("fastly-api-token", "Fastly", "Fastly API token", "MEDIUM",
     _assign("fastly", r"[a-z0-9\-=_]{32}"), ["fastly"], "secret"),
    ("finicity-client-secret", "Finicity", "Finicity client secret",
     "MEDIUM", _assign("finicity", r"[a-z0-9]{20}"), ["finicity"], "secret"),
    ("finicity-api-token", "Finicity", "Finicity API token", "MEDIUM",
     _assign("finicity", r"[a-f0-9]{32}"), ["finicity"], "secret"),
    ("flutterwave-public-key", "Flutterwave", "Flutterwave public/secret key",
     "MEDIUM", r"FLW(PUB|SEC)K_TEST-(?i)[a-h0-9]{32}-X", ["FLWPUBK_TEST",
                                                          "FLWSECK_TEST"],
     ""),
    ("flutterwave-enc-key", "Flutterwave", "Flutterwave encrypted key",
     "MEDIUM", r"FLWSECK_TEST[a-h0-9]{12}", ["FLWSECK_TEST"], ""),
    ("frameio-api-token", "FrameIO", "Frame.io API token", "LOW",
     r"fio-u-(?i)[a-z0-9\-_=]{64}", ["fio-u-"], ""),
    ("gocardless-api-token", "GoCardless", "GoCardless API token", "MEDIUM",
     _quoted(r"live_(?i)[a-z0-9\-_=]{40}"), ["gocardless"], ""),
    ("grafana-api-token", "Grafana", "Grafana API token", "MEDIUM",
     _quoted(r"eyJrIjoi(?i)[a-z0-9\-_=]{72,92}"), ["grafana"], ""),
    ("hashicorp-tf-api-token", "HashiCorp",
     "HashiCorp Terraform user/org API token", "MEDIUM",
     _quoted(r"(?i)[a-z0-9]{14}\.atlasv1\.[a-z0-9\-_=]{60,70}"),
     ["atlasv1"], ""),
    ("hubspot-api-token", "HubSpot", "HubSpot API token", "LOW",
     _assign("hubspot", UUID.lower().replace("a-f", "a-f")), ["hubspot"],
     "secret"),
    ("intercom-api-token", "Intercom", "Intercom API token", "LOW",
     _assign("intercom", r"[a-z0-9=_]{60}"), ["intercom"], "secret"),
    ("intercom-client-secret", "Intercom", "Intercom client secret/ID",
     "LOW", _assign("intercom", UUID), ["intercom"], "secret"),
    ("ionic-api-token", "Ionic", "Ionic API token", "MEDIUM",
     _assign("ionic", r"ion_[a-z0-9]{42}"), ["ion_"], "secret"),
    ("jwt-token", "JWT", "JWT token", "MEDIUM",
     r"ey[a-zA-Z0-9]{17,}\.ey[a-zA-Z0-9\/\\_-]{17,}\."
     r"(?:[a-zA-Z0-9\/\\_-]{10,}={0,2})?", ["jwt"], ""),
    ("linear-api-token", "Linear", "Linear API token", "MEDIUM",
     r"lin_api_(?i)[a-z0-9]{40}", ["lin_api_"], ""),
    ("linear-client-secret", "Linear", "Linear client secret/ID", "MEDIUM",
     _assign("linear", r"[a-f0-9]{32}"), ["linear"], "secret"),
    ("lob-api-key", "Lob", "Lob API Key", "LOW",
     _assign("lob", r"(live|test)_[a-f0-9]{35}"), ["lob"], "secret"),
    ("lob-pub-api-key", "Lob", "Lob Publishable API Key", "LOW",
     _assign("lob", r"(test|live)_pub_[a-f0-9]{31}"), ["lob"], "secret"),
    ("mailchimp-api-key", "Mailchimp", "Mailchimp API key", "MEDIUM",
     _assign("mailchimp", r"[a-f0-9]{32}-us[0-9]{1,2}"), ["mailchimp"],
     "secret"),
    ("mailgun-token", "Mailgun", "Mailgun private API token", "MEDIUM",
     _assign("mailgun", r"key-[a-f0-9]{32}"), ["mailgun"], "secret"),
    ("mailgun-signing-key", "Mailgun", "Mailgun webhook signing key",
     "MEDIUM",
     _assign("mailgun", r"[a-h0-9]{32}-[a-h0-9]{8}-[a-h0-9]{8}"),
     ["mailgun"], "secret"),
    ("mapbox-api-token", "Mapbox", "Mapbox API token", "MEDIUM",
     r"(?i)(pk\.[a-z0-9]{60}\.[a-z0-9]{22})", ["mapbox"], ""),
    ("messagebird-api-token", "MessageBird", "MessageBird API token",
     "MEDIUM", _assign("messagebird", r"[a-z0-9]{25}"), ["messagebird"],
     "secret"),
    ("messagebird-client-id", "MessageBird", "MessageBird API client ID",
     "MEDIUM", _assign("messagebird", UUID), ["messagebird"], "secret"),
    ("new-relic-user-api-key", "NewRelic", "New Relic user API Key",
     "MEDIUM", _quoted(r"NRAK-[A-Z0-9]{27}"), ["NRAK-"], ""),
    ("new-relic-user-api-id", "NewRelic", "New Relic user API ID", "MEDIUM",
     _assign("newrelic", r"[A-Z0-9]{64}"), ["newrelic"], "secret"),
    ("new-relic-browser-api-token", "NewRelic",
     "New Relic ingest browser API token", "MEDIUM",
     _quoted(r"NRJS-[a-f0-9]{19}"), ["NRJS-"], ""),
    ("npm-access-token", "Npm", "npm access token", "CRITICAL",
     r"(?i)" + _quoted(r"npm_[a-z0-9]{36}"), ["npm_"], ""),
    ("planetscale-password", "PlanetScale", "PlanetScale password", "MEDIUM",
     r"pscale_pw_(?i)[a-z0-9\-_\.]{43}", ["pscale_pw_"], ""),
    ("planetscale-api-token", "PlanetScale", "PlanetScale API token",
     "MEDIUM", r"pscale_tkn_(?i)[a-z0-9\-_\.]{43}", ["pscale_tkn_"], ""),
    ("postman-api-token", "Postman", "Postman API token", "MEDIUM",
     r"PMAK-(?i)[a-f0-9]{24}\-[a-f0-9]{34}", ["PMAK-"], ""),
    ("pulumi-api-token", "Pulumi", "Pulumi API token", "HIGH",
     r"pul-[a-f0-9]{40}", ["pul-"], ""),
    ("rubygems-api-token", "Rubygems", "Rubygem API token", "MEDIUM",
     r"rubygems_[a-f0-9]{48}", ["rubygems_"], ""),
    ("sendgrid-api-token", "SendGrid", "SendGrid API token", "MEDIUM",
     r"SG\.(?i)[a-z0-9_\-\.]{66}", ["SG."], ""),
    ("sendinblue-api-token", "SendinBlue", "Sendinblue API token", "LOW",
     r"xkeysib-[a-f0-9]{64}\-(?i)[a-z0-9]{16}", ["xkeysib-"], ""),
    ("shippo-api-token", "Shippo", "Shippo API token", "LOW",
     r"shippo_(live|test)_[a-f0-9]{40}", ["shippo_live_", "shippo_test_"],
     ""),
    ("linkedin-client-secret", "LinkedIn", "LinkedIn Client secret", "LOW",
     _assign("linkedin", r"[a-z]{16}"), ["linkedin"], "secret"),
    ("linkedin-client-id", "LinkedIn", "LinkedIn Client ID", "LOW",
     _assign("linkedin", r"[a-z0-9]{14}"), ["linkedin"], "secret"),
    ("twitch-api-token", "Twitch", "Twitch API token", "LOW",
     _assign("twitch", r"[a-z0-9]{30}"), ["twitch"], "secret"),
    ("typeform-api-token", "Typeform", "Typeform API token", "LOW",
     _assign("typeform", r"tfp_[a-z0-9\-_\.=]{59}"), ["typeform"], "secret"),
    ("dockerconfig-secret", "Docker", "Dockerconfig secret exposed", "HIGH",
     r"(?i)(\.(dockerconfigjson|dockercfg):\s*\|*\s*"
     r"(?P<secret>(ey|ew)+[A-Za-z0-9\/\+=]+))", ["dockerc"], "secret"),
]


def _goflags(pattern: str, top: bool = True) -> str:
    """Translate Go's mid-pattern `(?i)` into Python syntax.

    In Go a bare flag group applies from its position to the END OF THE
    ENCLOSING GROUP (e.g. `(?P<secret>(LTAI)(?i)[a-z0-9]{20})` leaves
    `LTAI` case-sensitive). Python only allows bare flags at position 0,
    so the scoped remainder is wrapped in `(?i:...)`."""
    i = pattern.find("(?i)")
    if i == -1 or (top and i == 0):
        return pattern
    j = i + 4
    # scan to the end of the enclosing group (unmatched ')') honoring
    # escapes and character classes
    depth = 0
    in_class = False
    k = j
    while k < len(pattern):
        c = pattern[k]
        if c == "\\":
            k += 2
            continue
        if in_class:
            if c == "]":
                in_class = False
        elif c == "[":
            in_class = True
        elif c == "(":
            depth += 1
        elif c == ")":
            if depth == 0:
                break
            depth -= 1
        k += 1
    inner = _goflags(pattern[j:k], top=False)
    rest = _goflags(pattern[k:], top=False)
    return pattern[:i] + "(?i:" + inner + ")" + rest


_NAMED_GROUP = re.compile(r"\(\?P<([A-Za-z_]\w*)>")


def _dedup_groups(pattern: str):
    """Go regexps may reuse a group name; Python forbids it. Rename
    later occurrences name → name__N and report the alias map so
    secret-group extraction can follow every occurrence."""
    seen: dict[str, int] = {}
    aliases: dict[str, list[str]] = {}
    out = []
    last = 0
    for m in _NAMED_GROUP.finditer(pattern):
        name = m.group(1)
        n = seen.get(name, 0) + 1
        seen[name] = n
        if n > 1:
            new = f"{name}__{n}"
            out.append(pattern[last:m.start()] + f"(?P<{new}>")
            last = m.end()
            aliases.setdefault(name, []).append(new)
    out.append(pattern[last:])
    return "".join(out), aliases


def compile_rule_regex(pattern: str):
    """→ (compiled regex, group alias map) with Go-compat fixups."""
    pattern, aliases = _dedup_groups(pattern)
    return re.compile(_goflags(pattern)), aliases


def _build() -> list[Rule]:
    rules = []
    for rid, cat, title, sev, pattern, keywords, group in _TABLE:
        rx, aliases = compile_rule_regex(pattern)
        rules.append(Rule(
            id=rid, category=cat, title=title, severity=sev,
            regex=rx, keywords=list(keywords),
            secret_group=group,
            secret_aliases=tuple(aliases.get(group, ()))))
    return rules


BUILTIN_RULES: list[Rule] = _build()


def load_secret_config(path: str):
    """trivy-secret.yaml → (rules, global_allow_rules,
    global_exclude_regexes). Schema mirrors the reference secret.Config
    (pkg/fanal/secret/scanner.go:27-41): enable-builtin-rules restricts
    the builtin set, disable-rules and disable-allow-rules remove by id
    (from the global AND per-rule allow sets), `rules` / `allow-rules`
    append custom entries, `exclude-block` (global and per-rule) strips
    matching text regions before reporting."""
    import dataclasses

    import yaml
    with open(path) as f:
        doc = yaml.safe_load(f) or {}
    rules = list(BUILTIN_RULES)
    enable = doc.get("enable-builtin-rules") or []
    if enable:
        keep = set(enable)
        rules = [r for r in rules if r.id in keep]
    for rd in doc.get("rules") or []:
        rx, aliases = compile_rule_regex(rd.get("regex", ""))
        rules.append(Rule(
            id=rd.get("id", ""), category=rd.get("category", ""),
            title=rd.get("title", ""), severity=rd.get("severity", ""),
            regex=rx,
            keywords=list(rd.get("keywords") or []),
            secret_group=rd.get("secret-group-name", ""),
            secret_aliases=tuple(
                aliases.get(rd.get("secret-group-name", ""), ())),
            path=re.compile(rd["path"]) if rd.get("path") else None,
            allow_rules=[_allow_from_dict(a)
                         for a in rd.get("allow-rules") or []],
            exclude_regexes=[
                re.compile(rx) for rx in
                (rd.get("exclude-block") or {}).get("regexes") or []],
        ))
    # disable-rules applies to builtin AND custom ids (the reference
    # filters after merging, scanner.go NewScanner)
    disable = set(doc.get("disable-rules") or [])
    rules = [r for r in rules if r.id not in disable]
    disable_allow = set(doc.get("disable-allow-rules") or [])
    if disable_allow:
        # applies to per-rule allow sets too (scanner.go NewScanner)
        rules = [
            dataclasses.replace(r, allow_rules=[
                a for a in r.allow_rules if a.id not in disable_allow])
            if any(a.id in disable_allow for a in r.allow_rules) else r
            for r in rules
        ]
    allow = [a for a in GLOBAL_ALLOW_RULES if a.id not in disable_allow]
    allow.extend(_allow_from_dict(a) for a in doc.get("allow-rules") or [])
    exclude = [re.compile(rx) for rx in
               (doc.get("exclude-block") or {}).get("regexes") or []]
    return rules, allow, exclude


def _allow_from_dict(a: dict) -> AllowRule:
    return AllowRule(
        a.get("id", ""), a.get("description", ""),
        regex=re.compile(a["regex"]) if a.get("regex") else None,
        path=re.compile(a["path"]) if a.get("path") else None)
