/* Native admin TUI — ANSI/termios, no curses dependency.
 *
 * Re-creates the reference dashboard's semantics (tui.rs) on top of the
 * TPU engine: the backends panel becomes a CHIPS/MODELS panel showing HBM
 * occupancy, decode step latency, and tok/s per model runtime instead of
 * Ollama URL status. Key map preserved from the reference
 * (tui.rs:102-303):
 *
 *   q/Esc quit (whole app)     ?        toggle help
 *   Tab/h/l  cycle panel       j/k      move selection
 *   Space/Enter expand model detail
 *   p  VIP toggle on selected user (clears boost only if the SAME user
 *      held it — tui.rs:169-175)
 *   b  boost toggle (symmetric — tui.rs:196-202)
 *   x  block selected user     X  block selected user's IP
 *   u  unblock selected blocked item
 *
 * Data feeds: the mqcore snapshot (same-process, via mq_snapshot_json)
 * and an engine-stats callback provided by the embedding Python process
 * (model runtimes, HBM, step latency). Rendering double-buffers into a
 * string and writes one frame per refresh to avoid flicker; input is
 * select(2)-polled at the reference's 100 ms cadence (tui.rs:112).
 */

#include <sys/ioctl.h>
#include <sys/select.h>
#include <termios.h>
#include <unistd.h>

#include <algorithm>
#include <cstdio>
#include <cstring>
#include <ctime>
#include <string>
#include <vector>

#include "minijson.h"
#include "mqcore.h"

extern "C" {
typedef long long (*mq_stats_cb)(char *buf, long long cap);
int mqtui_run(mq_state *state, mq_stats_cb stats_cb, int refresh_ms);
}

namespace {

struct TermGuard {
  termios orig{};
  bool ok = false;
  TermGuard() {
    if (tcgetattr(STDIN_FILENO, &orig) == 0) {
      termios raw = orig;
      raw.c_lflag &= ~(ICANON | ECHO);
      raw.c_cc[VMIN] = 0;
      raw.c_cc[VTIME] = 0;
      tcsetattr(STDIN_FILENO, TCSANOW, &raw);
      ok = true;
    }
    // Alt screen + hide cursor.
    (void)!write(STDOUT_FILENO, "\x1b[?1049h\x1b[?25l", 14);
  }
  ~TermGuard() {
    (void)!write(STDOUT_FILENO, "\x1b[?1049l\x1b[?25h", 14);
    if (ok) tcsetattr(STDIN_FILENO, TCSANOW, &orig);
  }
};

struct UserRow {
  std::string name;
  long long queued = 0, processing = 0, processed = 0, dropped = 0, tokens = 0;
  std::string ip;
};

// Colors.
const char *RST = "\x1b[0m";
const char *BOLD = "\x1b[1m";
const char *DIM = "\x1b[2m";
const char *CYAN = "\x1b[36m";
const char *GREEN = "\x1b[32m";
const char *YELLOW = "\x1b[33m";
const char *RED = "\x1b[31m";
const char *MAGENTA = "\x1b[35m";
const char *INV = "\x1b[7m";

std::string pad(const std::string &s, size_t w) {
  // Width-naive truncate/pad (ASCII data; user ids clipped hard).
  if (s.size() >= w) return s.substr(0, w);
  return s + std::string(w - s.size(), ' ');
}

std::string human_bytes(double b) {
  char buf[32];
  if (b >= 1e9) std::snprintf(buf, sizeof buf, "%.1fG", b / 1e9);
  else if (b >= 1e6) std::snprintf(buf, sizeof buf, "%.0fM", b / 1e6);
  else if (b >= 1e3) std::snprintf(buf, sizeof buf, "%.0fK", b / 1e3);
  else std::snprintf(buf, sizeof buf, "%.0fB", b);
  return buf;
}

struct Tui {
  mq_state *state;
  mq_stats_cb stats_cb;
  int panel = 0;  // 0 chips/models, 1 users, 2 queues, 3 blocked
  int sel[4] = {0, 0, 0, 0};
  bool expanded = false;
  bool help = false;
  // tok/s rate from successive tokens_generated samples.
  double last_tokens = -1;
  double tok_rate = 0;
  timespec last_sample{};

  std::string frame;

  void put(const std::string &s) { frame += s; }
  void line(const std::string &s, int width) {
    frame += pad_visible(s, width);
    frame += "\x1b[K\r\n";
  }

  // pad to visible width ignoring escape sequences
  static std::string pad_visible(const std::string &s, int width) {
    int vis = 0;
    std::string out;
    for (size_t i = 0; i < s.size();) {
      if (s[i] == '\x1b') {
        size_t j = i + 1;
        while (j < s.size() && s[j] != 'm') ++j;
        out += s.substr(i, j - i + 1);
        i = j + 1;
      } else {
        if (vis < width) {
          out += s[i];
          ++vis;
        }
        ++i;
      }
    }
    while (vis < width) {
      out += ' ';
      ++vis;
    }
    return out;
  }

  mj::ValuePtr snapshot() {
    long long need = mq_snapshot_json(state, nullptr, 0);
    std::string buf(need + 16, '\0');
    mq_snapshot_json(state, buf.data(), (long long)buf.size());
    buf.resize(std::strlen(buf.c_str()));
    return mj::parse(buf);
  }

  bool quit_requested = false;

  mj::ValuePtr engine_stats() {
    if (!stats_cb) return std::make_shared<mj::Value>();
    std::string buf(65536, '\0');
    long long n = stats_cb(buf.data(), (long long)buf.size());
    if (n == -9) {  // embedder requests shutdown (e.g. Ctrl-C in Python)
      quit_requested = true;
      return std::make_shared<mj::Value>();
    }
    // Bounds-check hard: a failed ctypes callback can return garbage.
    if (n <= 0 || n >= (long long)buf.size())
      return std::make_shared<mj::Value>();
    buf.resize((size_t)n);
    return mj::parse(buf);
  }

  std::vector<UserRow> user_rows(const mj::ValuePtr &snap) {
    std::vector<UserRow> rows;
    auto users = snap->get("users");
    if (!users) return rows;
    for (auto &kv : users->obj) {
      UserRow r;
      r.name = kv.first;
      auto &u = kv.second;
      r.queued = u->get("queued") ? u->get("queued")->as_int() : 0;
      r.processing = u->get("processing") ? u->get("processing")->as_int() : 0;
      r.processed = u->get("processed") ? u->get("processed")->as_int() : 0;
      r.dropped = u->get("dropped") ? u->get("dropped")->as_int() : 0;
      r.tokens = u->get("tokens") ? u->get("tokens")->as_int() : 0;
      if (u->get("ip")) r.ip = u->get("ip")->as_str();
      rows.push_back(std::move(r));
    }
    // Reference ordering (tui.rs:76-85): active first (queued+processing
    // desc), then lifetime (processed+dropped desc), then name.
    std::sort(rows.begin(), rows.end(), [](const UserRow &a, const UserRow &b) {
      long long aa = a.queued + a.processing, bb = b.queued + b.processing;
      if (aa != bb) return aa > bb;
      long long al = a.processed + a.dropped, bl = b.processed + b.dropped;
      if (al != bl) return al > bl;
      return a.name < b.name;
    });
    return rows;
  }

  void render(int rows, int cols) {
    frame.clear();
    put("\x1b[H");  // home

    auto snap = snapshot();
    auto stats = engine_stats();
    auto users = user_rows(snap);
    std::string vip = snap->get("vip") && !snap->get("vip")->is_null()
                          ? snap->get("vip")->as_str() : "";
    std::string boost = snap->get("boost") && !snap->get("boost")->is_null()
                            ? snap->get("boost")->as_str() : "";

    // ---- stats bar ----
    long long tq = 0, tp = 0, tdone = 0, tdrop = 0, ttok = 0;
    for (auto &u : users) {
      tq += u.queued; tp += u.processing; tdone += u.processed;
      tdrop += u.dropped; ttok += u.tokens;
    }
    // tok/s from engine counter deltas.
    double tokens_now = 0;
    auto models = stats->get("models");
    if (models)
      for (auto &m : models->arr)
        tokens_now += m->get("tokens_generated")
                          ? m->get("tokens_generated")->as_num() : 0;
    timespec now{};
    clock_gettime(CLOCK_MONOTONIC, &now);
    if (last_tokens >= 0) {
      double dt = (now.tv_sec - last_sample.tv_sec) +
                  (now.tv_nsec - last_sample.tv_nsec) / 1e9;
      if (dt > 0.5) {
        tok_rate = 0.7 * tok_rate + 0.3 * ((tokens_now - last_tokens) / dt);
        last_tokens = tokens_now;
        last_sample = now;
      }
    } else {
      last_tokens = tokens_now;
      last_sample = now;
    }

    char bar[512];
    std::snprintf(bar, sizeof bar,
                  " ollamaMQ-TPU   queued %lld   processing %lld   served %lld   "
                  "dropped %lld   tok/s %.0f",
                  tq, tp, tdone, tdrop, tok_rate > 0 ? tok_rate : 0.0);
    put(std::string(BOLD) + INV);
    line(bar, cols);
    put(RST);

    if (help) {
      render_help(rows, cols);
      return;
    }

    // ---- three columns: chips/models | users | queues ----
    int col1 = cols * 35 / 100, col2 = cols * 35 / 100;
    int col3 = cols - col1 - col2 - 2;
    int body = rows - 2 /*bars*/ - 6 /*blocked + headers*/ - 3 /*alerts*/
               - 1 /*last-decision line*/;
    if (body < 4) body = 4;

    std::vector<std::string> c1 = render_models(stats, col1, body);
    std::vector<std::string> c2 = render_users(users, vip, boost, col2, body);
    std::vector<std::string> c3 = render_queues(users, tq, col3, body);
    for (int i = 0; i < body; ++i) {
      std::string l;
      l += pad_visible(i < (int)c1.size() ? c1[i] : "", col1);
      l += "\x1b[2m|\x1b[0m";
      l += pad_visible(i < (int)c2.size() ? c2[i] : "", col2);
      l += "\x1b[2m|\x1b[0m";
      l += pad_visible(i < (int)c3.size() ? c3[i] : "", col3);
      line(l, cols);
    }

    // ---- flight recorder: newest scheduler decision, full width (the
    // explain() one-liner from the engine's decision journal; fixed one
    // row so the layout never jumps) ----
    auto last = stats->get("last_decision");
    if (last && last->type == mj::Value::STR && !last->str.empty())
      line(std::string(DIM) + " last: " + last->str + RST, cols);
    else
      line(std::string(DIM) + " last: (no decisions yet)" + RST, cols);

    // ---- alerts (SLO burn-rate + stall watchdog, via the engine's
    // shared alert table; ok when quiet, red rows when firing) ----
    render_alerts(stats, cols);

    // ---- blocked items ----
    put(std::string(BOLD));
    line(panel == 3 ? "> BLOCKED ITEMS" : "  BLOCKED ITEMS", cols);
    put(RST);
    std::vector<std::string> blocked;
    if (snap->get("blocked_users"))
      for (auto &b : snap->get("blocked_users")->arr)
        blocked.push_back("user " + b->as_str());
    if (snap->get("blocked_ips"))
      for (auto &b : snap->get("blocked_ips")->arr)
        blocked.push_back("ip   " + b->as_str());
    if (sel[3] >= (int)blocked.size()) sel[3] = blocked.empty() ? 0 : blocked.size() - 1;
    for (int i = 0; i < 3; ++i) {
      if (i < (int)blocked.size()) {
        std::string marker = (panel == 3 && i == sel[3]) ? "> " : "  ";
        line(marker + std::string(RED) + "✖ " + RST + blocked[i], cols);
      } else {
        line(i == 0 && blocked.empty() ? std::string(DIM) + "  (none)" + RST : "", cols);
      }
    }

    // ---- help bar ----
    put(DIM);
    line(" q quit  ? help  Tab panel  j/k move  p VIP  b boost  x block  X block-ip  u unblock  Space expand",
         cols);
    put(RST);
  }

  std::vector<std::string> render_models(const mj::ValuePtr &stats, int w, int body) {
    std::vector<std::string> out;
    std::string hdr = panel == 0 ? "> CHIPS / MODELS" : "  CHIPS / MODELS";
    out.push_back(std::string(BOLD) + hdr + RST);
    double hbm_used = stats->get("hbm_used") ? stats->get("hbm_used")->as_num() : 0;
    double hbm_total = stats->get("hbm_total") ? stats->get("hbm_total")->as_num() : 0;
    std::string dev = stats->get("device") ? stats->get("device")->as_str() : "?";
    char l[256];
    if (hbm_total > 0)
      std::snprintf(l, sizeof l, " %s  HBM %s/%s (%.0f%%)", dev.c_str(),
                    human_bytes(hbm_used).c_str(), human_bytes(hbm_total).c_str(),
                    100.0 * hbm_used / hbm_total);
    else
      std::snprintf(l, sizeof l, " %s  HBM %s", dev.c_str(),
                    human_bytes(hbm_used).c_str());
    out.push_back(std::string(CYAN) + l + RST);
    /* Throughput + MFU: the "is the pod earning its keep" line. MFU is
     * the max over runtimes (fraction 0..1 from the engine's analytic
     * FLOPs model over chip peak); 0 renders as "--" (unknown peak, e.g.
     * CPU meshes, or no decode step yet). */
    double mfu = 0;
    /* Prefix-cache hit ratio: summed hits/misses over runtimes that
     * cache ("prefix_cache" non-null). No caching runtime => "n/a". */
    bool cache_on = false;
    double cache_hits = 0, cache_lookups = 0;
    auto models_mfu = stats->get("models");
    if (models_mfu)
      for (auto &m : models_mfu->arr) {
        double v = m->get("mfu") ? m->get("mfu")->as_num() : 0;
        if (v > mfu) mfu = v;
        auto pc = m->get("prefix_cache");
        if (pc && !pc->is_null()) {
          cache_on = true;
          double h = pc->get("hits") ? pc->get("hits")->as_num() : 0;
          double mi = pc->get("misses") ? pc->get("misses")->as_num() : 0;
          cache_hits += h;
          cache_lookups += h + mi;
        }
      }
    char cache[32];
    if (!cache_on)
      std::snprintf(cache, sizeof cache, "cache n/a");
    else
      std::snprintf(cache, sizeof cache, "cache %.0f%%",
                    cache_lookups > 0 ? 100.0 * cache_hits / cache_lookups
                                      : 0.0);
    /* Degradation chip: requests shed (admission caps / deadlines / KV
     * exhaustion) and KV-pressure preemptions. Both nonzero is the
     * "saturated but degrading gracefully" signature; shed rising with
     * preempt flat means the queue caps are doing the shedding. */
    double shed = stats->get("shed") ? stats->get("shed")->as_num() : 0;
    double preempt =
        stats->get("preempt") ? stats->get("preempt")->as_num() : 0;
    char degrade[48];
    std::snprintf(degrade, sizeof degrade, "shed %.0f  preempt %.0f", shed,
                  preempt);
    /* Scheduler chip: active policy (fcfs/srpt/edf) + the output-length
     * predictor's accuracy over its recent window. "acc n/a" until the
     * predictor has observed enough finishes to warm up. */
    char schedc[64];
    auto sched = stats->get("sched");
    if (sched && sched->type == mj::Value::OBJ) {
      std::string pol =
          sched->get("policy") ? sched->get("policy")->as_str() : "?";
      auto acc = sched->get("pred_accuracy");
      if (acc && !acc->is_null())
        std::snprintf(schedc, sizeof schedc, "sched %s acc %.0f%%",
                      pol.c_str(), acc->as_num() * 100.0);
      else
        std::snprintf(schedc, sizeof schedc, "sched %s acc n/a",
                      pol.c_str());
    } else {
      std::snprintf(schedc, sizeof schedc, "sched n/a");
    }
    if (mfu > 0)
      std::snprintf(l, sizeof l,
                    " throughput %.0f tok/s   MFU %.2f%%   %s   %s   %s",
                    tok_rate > 0 ? tok_rate : 0.0, mfu * 100.0, cache, degrade,
                    schedc);
    else
      std::snprintf(l, sizeof l,
                    " throughput %.0f tok/s   MFU --   %s   %s   %s",
                    tok_rate > 0 ? tok_rate : 0.0, cache, degrade, schedc);
    out.push_back(std::string(CYAN) + l + RST);
    /* Engine performance plane chip (its own line, present once the
     * engine has dispatched or compiled): compile-ladder fill count +
     * rolling step p99 off the always-on step profiler. A compile
     * count still climbing in steady state is ladder thrash (the
     * compile_storm alert's TUI face). */
    auto sp = stats->get("stepprof");
    if (sp && sp->type == mj::Value::OBJ) {
      double comp =
          sp->get("compiles") ? sp->get("compiles")->as_num() : 0;
      /* ...of them, the first calls whose every program came out of
       * the persistent compilation cache, and those that compiled one. */
      double hit = sp->get("hit") ? sp->get("hit")->as_num() : 0;
      double miss = sp->get("miss") ? sp->get("miss")->as_num() : 0;
      auto sp99 = sp->get("p99_ms");
      if (sp99 && sp99->type == mj::Value::NUM)
        std::snprintf(l, sizeof l,
                      " compiles %.0f (%.0f hit / %.0f miss) · step p99 "
                      "%.2fms", comp, hit, miss, sp99->as_num());
      else
        std::snprintf(l, sizeof l,
                      " compiles %.0f (%.0f hit / %.0f miss) · step p99 n/a",
                      comp, hit, miss);
      out.push_back(std::string(CYAN) + l + RST);
    }
    /* Fleet replicas chip (only under a fleet router): N healthy / M
     * ejected / K draining. Red when any member is out of rotation —
     * capacity is reduced and streams may be mid-failover. */
    auto fleet = stats->get("replicas");
    if (fleet && fleet->type == mj::Value::OBJ) {
      double fh = fleet->get("healthy") ? fleet->get("healthy")->as_num() : 0;
      double fe = fleet->get("ejected") ? fleet->get("ejected")->as_num() : 0;
      double fd = fleet->get("draining") ? fleet->get("draining")->as_num() : 0;
      std::snprintf(l, sizeof l,
                    " replicas %.0f healthy / %.0f ejected / %.0f draining",
                    fh, fe, fd);
      out.push_back(std::string(fe > 0 ? RED : CYAN) + l + RST);
      /* Router-overhead chip (its own line — the chips column is a
       * third of the terminal): the windowed placement-decision p99 vs
       * its budget (ollamamq_router_overhead_ms{site="place"}). RED
       * when the router hot path itself is over budget — the fleet is
       * paying routing tax on every stream, not just serving slower. */
      auto ro = stats->get("router_overhead");
      if (ro && ro->type == mj::Value::OBJ) {
        bool over = false;
        if (ro->get("p99_ms") && ro->get("p99_ms")->type == mj::Value::NUM) {
          double p99 = ro->get("p99_ms")->as_num();
          double budget = ro->get("budget_ms")
                              ? ro->get("budget_ms")->as_num() : 0;
          over = budget > 0 && p99 > budget;
          if (budget > 0)
            std::snprintf(l, sizeof l,
                          " router p99 %.2fms (budget %.0fms)", p99, budget);
          else
            std::snprintf(l, sizeof l, " router p99 %.2fms", p99);
        } else {
          std::snprintf(l, sizeof l, " router p99 n/a");
        }
        out.push_back(std::string(over ? RED : CYAN) + l + RST);
      }
    }
    /* Fleet-size chip (elastic fleets only): current size against the
     * autoscaler's [min, max] band, plus how much of the fleet is
     * preemptible (spot) capacity that a reclamation notice can take. */
    auto fsz = stats->get("fleet_size");
    if (fsz && fsz->type == mj::Value::OBJ) {
      double fn = fsz->get("n") ? fsz->get("n")->as_num() : 0;
      double fp =
          fsz->get("preemptible") ? fsz->get("preemptible")->as_num() : 0;
      double fmin = fsz->get("min") ? fsz->get("min")->as_num() : 0;
      double fmax = fsz->get("max") ? fsz->get("max")->as_num() : 0;
      if (fp > 0)
        std::snprintf(l, sizeof l,
                      " fleet %.0f (+%.0f preemptible)  [%.0f..%.0f]",
                      fn, fp, fmin, fmax);
      else
        std::snprintf(l, sizeof l, " fleet %.0f  [%.0f..%.0f]", fn, fmin,
                      fmax);
      out.push_back(std::string(CYAN) + l + RST);
    }
    /* HA role chip (HA fleets only): role + fencing epoch, e.g.
     * "ha primary/3"; a standby adds its replication lag in records.
     * RED while "promoting" (takeover ladder in flight) and for a
     * standby that has not caught up to its primary's stream — in both
     * states the fleet is one failure away from dropping streams. */
    auto ha = stats->get("ha");
    if (ha && ha->type == mj::Value::OBJ) {
      std::string role = ha->get("role") ? ha->get("role")->str : "?";
      long long epoch = ha->get("epoch") ? ha->get("epoch")->as_int() : 0;
      bool synced = !ha->get("synced") ||
                    ha->get("synced")->type != mj::Value::BOOL ||
                    ha->get("synced")->b;
      auto lag = ha->get("lag");
      if (role != "primary" && lag && lag->type == mj::Value::NUM)
        std::snprintf(l, sizeof l, " ha %s/%lld  lag %.0f", role.c_str(),
                      epoch, lag->as_num());
      else
        std::snprintf(l, sizeof l, " ha %s/%lld", role.c_str(), epoch);
      bool alarm = role == "promoting" || (role == "standby" && !synced);
      out.push_back(std::string(alarm ? RED : CYAN) + l + RST);
    }
    /* Tiers line (tiered fleets only): healthy/total per replica tier.
     * RED when any tier has ZERO healthy members — that tier's traffic
     * is being served cross-tier (journaled overflow) until a member
     * heals or regroups in. */
    auto tiers = stats->get("tiers");
    if (tiers && tiers->type == mj::Value::OBJ) {
      std::string line = " tiers";
      bool starved = false;
      for (auto &kv : tiers->obj) {
        auto &t = kv.second;
        if (!t || t->type != mj::Value::OBJ) continue;
        double th = t->get("healthy") ? t->get("healthy")->as_num() : 0;
        double tt = t->get("total") ? t->get("total")->as_num() : 0;
        if (tt > 0 && th <= 0) starved = true;
        std::snprintf(l, sizeof l, "  %s %.0f/%.0f", kv.first.c_str(), th,
                      tt);
        line += l;
      }
      out.push_back(std::string(starved ? RED : CYAN) + line + RST);
    }
    /* One row PER chip (pod-wide under SPMD): the north star's "per-chip
     * HBM occupancy" — a v5e-16 must not show chip 0 for the pod. */
    auto chips = stats->get("chips");
    if (chips && !chips->arr.empty()) {
      /* Cap the rows so a big pod (v5e-64+) can't push the MODELS list —
       * the panel the admin verbs operate on — off a 40-row terminal. */
      int cap = body - 4 - (int)(stats->get("models")
                                     ? stats->get("models")->arr.size() : 0);
      if (cap < 2) cap = 2;
      int shown = 0;
      for (auto &c : chips->arr) {
        if (shown >= cap) break;
        long long id = c->get("id") ? c->get("id")->as_int() : 0;
        long long proc = c->get("process") ? c->get("process")->as_int() : 0;
        double cu = c->get("hbm_used") ? c->get("hbm_used")->as_num() : 0;
        double ct = c->get("hbm_total") ? c->get("hbm_total")->as_num() : 0;
        /* Backend without memory_stats (CPU): say "n/a", never a fake
         * 0-byte HBM reading. Missing key = legacy row = assume real. */
        auto ms = c->get("memory_stats");
        if (ms && ms->type == mj::Value::BOOL && !ms->b) {
          std::snprintf(l, sizeof l, "  chip %lld (host %lld)  HBM n/a",
                        id, proc);
          out.push_back(std::string(DIM) + l + RST);
          ++shown;
          continue;
        }
        if (ct > 0)
          std::snprintf(l, sizeof l, "  chip %lld (host %lld)  %s/%s (%.0f%%)",
                        id, proc, human_bytes(cu).c_str(),
                        human_bytes(ct).c_str(), 100.0 * cu / ct);
        else
          std::snprintf(l, sizeof l, "  chip %lld (host %lld)  %s", id, proc,
                        human_bytes(cu).c_str());
        out.push_back(std::string(DIM) + l + RST);
        ++shown;
      }
      if ((int)chips->arr.size() > shown) {
        std::snprintf(l, sizeof l, "  … +%d more chips",
                      (int)chips->arr.size() - shown);
        out.push_back(std::string(DIM) + l + RST);
      }
    }
    auto models = stats->get("models");
    if (!models) return out;
    int idx = 0;
    if (sel[0] >= (int)models->arr.size())
      sel[0] = models->arr.empty() ? 0 : models->arr.size() - 1;
    for (auto &m : models->arr) {
      std::string name = m->get("model") ? m->get("model")->as_str() : "?";
      long long act = m->get("active_slots") ? m->get("active_slots")->as_int() : 0;
      long long slots = m->get("max_slots") ? m->get("max_slots")->as_int() : 0;
      double step = m->get("step_latency_ms") ? m->get("step_latency_ms")->as_num() : 0;
      std::string marker = (panel == 0 && idx == sel[0]) ? "> " : "  ";
      const char *color = act > 0 ? GREEN : DIM;
      std::snprintf(l, sizeof l, "%s%s  %lld/%lld slots  %.1fms/step",
                    marker.c_str(), name.c_str(), act, slots, step);
      out.push_back(std::string(color) + l + RST);
      if (expanded && panel == 0 && idx == sel[0]) {
        long long pu = m->get("pages_used") ? m->get("pages_used")->as_int() : 0;
        long long pt = m->get("pages_total") ? m->get("pages_total")->as_int() : 0;
        double pb = m->get("param_bytes") ? m->get("param_bytes")->as_num() : 0;
        double kb = m->get("kv_bytes") ? m->get("kv_bytes")->as_num() : 0;
        long long pend = m->get("pending_prefill")
                             ? m->get("pending_prefill")->as_int() : 0;
        std::snprintf(l, sizeof l, "    KV pages %lld/%lld  prefillQ %lld", pu, pt, pend);
        out.push_back(std::string(DIM) + l + RST);
        std::snprintf(l, sizeof l, "    params %s  kv-pool %s",
                      human_bytes(pb).c_str(), human_bytes(kb).c_str());
        out.push_back(std::string(DIM) + l + RST);
        double pfms = m->get("prefill_latency_ms")
                          ? m->get("prefill_latency_ms")->as_num() : 0;
        double ttft50 = m->get("ttft_p50_ms") ? m->get("ttft_p50_ms")->as_num() : 0;
        double st50 = m->get("step_p50_ms") ? m->get("step_p50_ms")->as_num() : 0;
        std::snprintf(l, sizeof l, "    last prefill %.1fms  TTFT p50 %.0fms  step p50 %.1fms",
                      pfms, ttft50, st50);
        out.push_back(std::string(DIM) + l + RST);
      }
      ++idx;
      if ((int)out.size() >= body) break;
    }
    return out;
  }

  void render_alerts(const mj::ValuePtr &stats, int cols) {
    /* Fixed 3-row section (header + 2 rows) so the layout never jumps
     * when alerts come and go. Overflow collapses into a "+N more". */
    auto alerts = stats->get("alerts");
    size_t n = alerts ? alerts->arr.size() : 0;
    char hdr[64];
    if (n > 0)
      std::snprintf(hdr, sizeof hdr, "  ALERTS (%d firing)", (int)n);
    else
      std::snprintf(hdr, sizeof hdr, "  ALERTS");
    put(std::string(BOLD) + (n > 0 ? RED : ""));
    line(hdr, cols);
    put(RST);
    int shown = 0;
    const int cap = 2;
    if (alerts) {
      for (auto &a : alerts->arr) {
        if (shown >= cap) break;
        std::string name = a->get("name") ? a->get("name")->as_str() : "?";
        std::string sev =
            a->get("severity") ? a->get("severity")->as_str() : "?";
        std::string msg =
            a->get("message") ? a->get("message")->as_str() : "";
        long long age = a->get("age_s") ? a->get("age_s")->as_int() : 0;
        char l[512];
        std::snprintf(l, sizeof l, "  ⚠ [%s] %s (%llds): %s", sev.c_str(),
                      name.c_str(), age, msg.c_str());
        line(std::string(RED) + l + RST, cols);
        ++shown;
      }
      if ((int)n > shown) {
        char l[64];
        std::snprintf(l, sizeof l, "    … +%d more alert(s)",
                      (int)n - shown);
        line(std::string(RED) + l + RST, cols);
        ++shown;
      }
    }
    if (shown == 0) {
      line(std::string(DIM) + "  (none)" + RST, cols);
      ++shown;
    }
    for (; shown < cap; ++shown) line("", cols);
  }

  std::vector<std::string> render_users(const std::vector<UserRow> &users,
                                        const std::string &vip,
                                        const std::string &boost,
                                        int w, int body) {
    std::vector<std::string> out;
    std::string hdr = panel == 1 ? "> USERS" : "  USERS";
    out.push_back(std::string(BOLD) + hdr + RST);
    if (sel[1] >= (int)users.size()) sel[1] = users.empty() ? 0 : users.size() - 1;
    int idx = 0;
    for (auto &u : users) {
      std::string sym, color = DIM;
      if (u.name == vip) { sym += "★"; color = YELLOW; }
      if (u.name == boost) { sym += "⚡"; color = MAGENTA; }
      if (mq_is_user_blocked(state, u.name.c_str())) { sym += "✖"; color = RED; }
      if (u.processing > 0) { sym += "▶"; if (color == DIM) color = GREEN; }
      else if (u.queued > 0) { sym += "●"; if (color == DIM) color = CYAN; }
      std::string marker = (panel == 1 && idx == sel[1]) ? "> " : "  ";
      char l[256];
      std::snprintf(l, sizeof l, "%s%s %s  q%lld r%lld d%lld x%lld t%lld",
                    marker.c_str(), pad(u.name, 14).c_str(), pad(sym, 3).c_str(),
                    u.queued, u.processing, u.processed, u.dropped, u.tokens);
      out.push_back(color + l + RST);
      ++idx;
      if ((int)out.size() >= body) break;
    }
    if (users.empty())
      out.push_back(std::string(DIM) + "  (no users yet)" + RST);
    return out;
  }

  std::vector<std::string> render_queues(const std::vector<UserRow> &users,
                                         long long total_queued, int w, int body) {
    std::vector<std::string> out;
    std::string hdr = panel == 2 ? "> QUEUES" : "  QUEUES";
    out.push_back(std::string(BOLD) + hdr + RST);
    int barw = w - 22;
    if (barw < 5) barw = 5;
    int idx = 0;
    for (auto &u : users) {
      if (u.queued == 0 && idx >= 3) continue;
      // Reference scaling: 20 queued requests = full bar (tui.rs:529-547).
      int fill = (int)std::min<long long>(u.queued * barw / 20, barw);
      double pct = total_queued > 0 ? 100.0 * u.queued / total_queued : 0;
      char l[256];
      std::string bar = std::string(fill, '#') + std::string(barw - fill, ' ');
      std::snprintf(l, sizeof l, "  %s [%s] %3.0f%%",
                    pad(u.name, 10).c_str(), bar.c_str(), pct);
      out.push_back((u.queued > 0 ? std::string(CYAN) : std::string(DIM)) + l + RST);
      ++idx;
      if ((int)out.size() >= body) break;
    }
    return out;
  }

  void render_help(int rows, int cols) {
    const char *lines[] = {
      "",
      "  KEYS",
      "    q / Esc      quit (stops the whole server)",
      "    ?            toggle this help",
      "    Tab / h / l  cycle focused panel",
      "    j / k        move selection in the focused panel",
      "    Space/Enter  expand model details (chips panel)",
      "    p            toggle VIP on the selected user (absolute priority)",
      "    b            toggle Boost on the selected user (wins every 2nd tick)",
      "    x            block the selected user   (persists to blocked_items.json)",
      "    X            block the selected user's IP",
      "    u            unblock the selected blocked item",
      "",
      "  PANELS",
      "    CHIPS/MODELS  model runtimes on the TPU: slots, step latency, HBM",
      "    USERS         fair-share state: ★VIP ⚡boost ✖blocked ▶processing ●queued",
      "    QUEUES        per-user queue depth (full bar = 20 requests)",
      "    ALERTS        firing alerts: SLO burn-rate + stall watchdog",
      "    BLOCKED       persisted user/IP blocklist",
      "",
      "  press ? to return",
    };
    for (auto *l : lines) line(l, cols);
    for (int i = 0; i < rows - 2 - (int)(sizeof(lines) / sizeof(*lines)); ++i)
      line("", cols);
  }

  // ---- actions ----
  void act_on_key(char c, const std::vector<UserRow> &users,
                  const std::vector<std::string> &blocked_items,
                  const std::string &vip, const std::string &boost) {
    switch (c) {
      case '\t': case 'l': panel = (panel + 1) % 4; break;
      case 'h': panel = (panel + 3) % 4; break;
      case 'j': sel[panel] += 1; break;
      case 'k': if (sel[panel] > 0) sel[panel] -= 1; break;
      case ' ': case '\r': expanded = !expanded; break;
      case '?': help = !help; break;
      case 'p': {
        if (panel == 1 && sel[1] < (int)users.size()) {
          const std::string &u = users[sel[1]].name;
          if (vip == u) {
            mq_set_vip(state, nullptr);
          } else {
            mq_set_vip(state, u.c_str());
            if (boost == u) mq_set_boost(state, nullptr);  // tui.rs:169-175
          }
        }
        break;
      }
      case 'b': {
        if (panel == 1 && sel[1] < (int)users.size()) {
          const std::string &u = users[sel[1]].name;
          if (boost == u) {
            mq_set_boost(state, nullptr);
          } else {
            mq_set_boost(state, u.c_str());
            if (vip == u) mq_set_vip(state, nullptr);  // tui.rs:196-202
          }
        }
        break;
      }
      case 'x': {
        if (panel == 1 && sel[1] < (int)users.size())
          mq_block_user(state, users[sel[1]].name.c_str());
        break;
      }
      case 'X': {
        if (panel == 1 && sel[1] < (int)users.size() &&
            !users[sel[1]].ip.empty())
          mq_block_ip(state, users[sel[1]].ip.c_str());
        break;
      }
      case 'u': {
        if (panel == 3 && sel[3] < (int)blocked_items.size())
          mq_unblock_item(state, blocked_items[sel[3]].c_str());
        break;
      }
    }
  }
};

}  // namespace

extern "C" int mqtui_run(mq_state *state, mq_stats_cb stats_cb, int refresh_ms) {
  if (!isatty(STDIN_FILENO) || !isatty(STDOUT_FILENO)) return 1;
  TermGuard guard;
  Tui tui;
  tui.state = state;
  tui.stats_cb = stats_cb;
  if (refresh_ms <= 0) refresh_ms = 100;

  while (true) {
    winsize ws{};
    ioctl(STDOUT_FILENO, TIOCGWINSZ, &ws);
    int rows = ws.ws_row > 0 ? ws.ws_row : 24;
    int cols = ws.ws_col > 0 ? ws.ws_col : 80;
    tui.render(rows, cols);
    if (tui.quit_requested) return 0;
    (void)!write(STDOUT_FILENO, tui.frame.data(), tui.frame.size());

    fd_set rfds;
    FD_ZERO(&rfds);
    FD_SET(STDIN_FILENO, &rfds);
    timeval tv{refresh_ms / 1000, (refresh_ms % 1000) * 1000};
    int r = select(STDIN_FILENO + 1, &rfds, nullptr, nullptr, &tv);
    if (r > 0) {
      char c = 0;
      if (read(STDIN_FILENO, &c, 1) == 1) {
        if (c == 'q' || c == '\x1b') {
          // Check for a bare Esc (not an escape sequence).
          if (c == '\x1b') {
            char seq[2];
            timeval zero{0, 0};
            fd_set f2;
            FD_ZERO(&f2);
            FD_SET(STDIN_FILENO, &f2);
            if (select(STDIN_FILENO + 1, &f2, nullptr, nullptr, &zero) > 0) {
              (void)!read(STDIN_FILENO, seq, 2);  // swallow arrow keys etc.
              continue;
            }
          }
          return 0;  // quit => caller stops the whole app (main.rs:174-177)
        }
        // Need fresh data for the action context.
        auto snap = tui.snapshot();
        auto users = tui.user_rows(snap);
        std::vector<std::string> blocked;
        if (snap->get("blocked_users"))
          for (auto &b : snap->get("blocked_users")->arr)
            blocked.push_back(b->as_str());
        if (snap->get("blocked_ips"))
          for (auto &b : snap->get("blocked_ips")->arr)
            blocked.push_back(b->as_str());
        std::string vip = snap->get("vip") && !snap->get("vip")->is_null()
                              ? snap->get("vip")->as_str() : "";
        std::string boost = snap->get("boost") && !snap->get("boost")->is_null()
                                ? snap->get("boost")->as_str() : "";
        tui.act_on_key(c, users, blocked, vip, boost);
      }
    }
  }
}
