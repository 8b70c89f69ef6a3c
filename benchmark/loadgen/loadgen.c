/* The benchmark's own HTTP load generator: one thread, epoll, keep-alive
 * connections to 127.0.0.1. Called through ctypes, which releases the
 * GIL, so the server's Python threads never compete with the client.
 *
 * Derived from es_loadgen (elasticsearch_tpu/native/src/estpu_http.cpp),
 * which is a closed loop. This one runs either
 *   - an OPEN loop: request i is due at start + due_ns[i], whatever the
 *     server is doing; it goes out on the first idle connection at or
 *     after its due time, and its latency is taken from the due time, so
 *     a stall charges every request that queued behind it; or
 *   - a CLOSED loop: n_conns connections each send the next request as
 *     soon as the previous answer is in, until `seconds` have passed.
 * Request i always carries body i. After the last send it waits up to
 * `drain_s` for the answers still out; one that has not come by then is
 * counted as never come (status 0).
 *
 * *out_t0 receives the start on CLOCK_MONOTONIC. Per request it records
 * send and completion times (ns after the start), the HTTP status (-1:
 * the connection failed, 0: never came) and whether the body has the
 * shape of a search answer. Bodies of requests with keep[i] != 0 are
 * copied into keep_buf for the correctness check.
 *
 * Build: cc -O2 -shared -fPIC (see __init__.py).
 */
#define _GNU_SOURCE
#include <errno.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <strings.h>
#include <sys/epoll.h>
#include <sys/socket.h>
#include <sys/timerfd.h>
#include <time.h>
#include <unistd.h>
#include <arpa/inet.h>
#include <fcntl.h>

typedef struct {
    int fd;
    long long req;       /* request in flight, -1 when idle */
    size_t woff, wlen;
    const char *wbuf;
    char *rbuf;
    size_t rlen, rcap;
    char head[256];
    size_t hlen;
} conn_t;

static int64_t now_ns(void) {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return (int64_t)ts.tv_sec * 1000000000LL + ts.tv_nsec;
}

static int dial(int port) {
    int fd = socket(AF_INET, SOCK_STREAM, 0);
    if (fd < 0) return -1;
    struct sockaddr_in a;
    memset(&a, 0, sizeof a);
    a.sin_family = AF_INET;
    a.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
    a.sin_port = htons((uint16_t)port);
    if (connect(fd, (struct sockaddr *)&a, sizeof a) != 0) {
        close(fd);
        return -1;
    }
    int one = 1;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
    fcntl(fd, F_SETFL, fcntl(fd, F_GETFL) | O_NONBLOCK);
    return fd;
}

static void watch(int ep, conn_t *c, uint32_t events, int op) {
    struct epoll_event ev;
    memset(&ev, 0, sizeof ev);
    ev.events = events;
    ev.data.ptr = c;
    epoll_ctl(ep, op, c->fd, &ev);
}

/* Content-Length of a complete header block, or -1 */
static long long content_length(const char *h, size_t hl) {
    const char *p = h;
    const char *end = h + hl;
    while (p < end) {
        const char *eol = memmem(p, (size_t)(end - p), "\r\n", 2);
        if (!eol) eol = end;
        if ((size_t)(eol - p) > 15 && strncasecmp(p, "content-length:", 15) == 0)
            return strtoll(p + 15, NULL, 10);
        p = eol + 2;
    }
    return -1;
}

typedef struct {
    const char *blob;
    const int64_t *offs;
    const char *path;
    const uint8_t *keep;
    char *keep_buf;
    int64_t keep_cap, keep_used;
    int64_t *keep_off;   /* [n][2]: offset, length (-1: did not fit) */
    int64_t *send_ns, *done_ns;
    int32_t *status;
    uint8_t *shaped;
    int64_t t0;
} run_t;

static void start_req(run_t *r, conn_t *c, long long i, int ep) {
    int64_t bl = r->offs[i + 1] - r->offs[i];
    c->hlen = (size_t)snprintf(c->head, sizeof c->head,
                               "POST %s HTTP/1.1\r\nHost: localhost\r\n"
                               "Content-Type: application/json\r\n"
                               "Content-Length: %lld\r\n\r\n",
                               r->path, (long long)bl);
    c->req = i;
    c->woff = 0;
    c->wbuf = r->blob + r->offs[i];
    c->wlen = (size_t)bl;
    c->rlen = 0;
    r->send_ns[i] = now_ns() - r->t0;
    watch(ep, c, EPOLLIN | EPOLLOUT, EPOLL_CTL_MOD);
}

/* write what the socket takes; 1 when the request is fully out */
static int pump_write(conn_t *c) {
    size_t total = c->hlen + c->wlen;
    while (c->woff < total) {
        ssize_t w;
        if (c->woff < c->hlen)
            w = write(c->fd, c->head + c->woff, c->hlen - c->woff);
        else
            w = write(c->fd, c->wbuf + (c->woff - c->hlen),
                      total - c->woff);
        if (w > 0) { c->woff += (size_t)w; continue; }
        if (w < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) return 0;
        return -1;
    }
    return 1;
}

/* read what is there; 1 when a whole response is in, -1 on a dead
 * connection */
static int pump_read(conn_t *c) {
    for (;;) {
        if (c->rcap - c->rlen < 65536) {
            c->rcap = c->rcap * 2 + 65536;
            c->rbuf = realloc(c->rbuf, c->rcap);
        }
        ssize_t n = read(c->fd, c->rbuf + c->rlen, c->rcap - c->rlen);
        if (n > 0) { c->rlen += (size_t)n; continue; }
        if (n == 0) return -1;
        if (errno == EAGAIN || errno == EWOULDBLOCK) break;
        return -1;
    }
    char *he = memmem(c->rbuf, c->rlen, "\r\n\r\n", 4);
    if (!he) return 0;
    size_t hl = (size_t)(he - c->rbuf);
    long long cl = content_length(c->rbuf, hl);
    if (cl < 0) return -1;
    return c->rlen >= hl + 4 + (size_t)cl ? 1 : 0;
}

static void finish(run_t *r, conn_t *c) {
    long long i = c->req;
    r->done_ns[i] = now_ns() - r->t0;
    int code = 0;
    if (c->rlen > 12) code = atoi(c->rbuf + 9);
    r->status[i] = code;
    char *he = memmem(c->rbuf, c->rlen, "\r\n\r\n", 4);
    const char *body = he + 4;
    size_t bl = c->rlen - (size_t)(body - c->rbuf);
    r->shaped[i] = code == 200 && bl > 0 && body[0] == '{'
                   && memmem(body, bl, "\"hits\"", 6) != NULL;
    if (r->keep && r->keep[i]) {
        if (r->keep_used + (int64_t)bl <= r->keep_cap) {
            memcpy(r->keep_buf + r->keep_used, body, bl);
            r->keep_off[2 * i] = r->keep_used;
            r->keep_off[2 * i + 1] = (int64_t)bl;
            r->keep_used += (int64_t)bl;
        } else {
            r->keep_off[2 * i + 1] = -1;
        }
    }
    c->req = -1;
    c->rlen = 0;
}

static void fail(run_t *r, conn_t *c, int ep, int port) {
    if (c->req >= 0) {
        r->status[c->req] = -1;
        r->done_ns[c->req] = now_ns() - r->t0;
    }
    epoll_ctl(ep, EPOLL_CTL_DEL, c->fd, NULL);
    close(c->fd);
    c->req = -1;
    c->rlen = 0;
    c->fd = dial(port);
    if (c->fd >= 0) watch(ep, c, EPOLLIN, EPOLL_CTL_ADD);
}

/* Returns the number of requests sent, or -1 when no connection could
 * be opened. due_ns == NULL selects the closed loop. */
long long lg_run(int port, const char *path, const char *blob,
                 const int64_t *offs, long long n_req,
                 const int64_t *due_ns, int n_conns, double seconds,
                 double drain_s, const uint8_t *keep, char *keep_buf,
                 int64_t keep_cap, int64_t *keep_off, int64_t *send_ns,
                 int64_t *done_ns, int32_t *status, uint8_t *shaped,
                 int64_t *out_t0) {
    run_t r = {blob, offs, path, keep, keep_buf, keep_cap, 0, keep_off,
               send_ns, done_ns, status, shaped, 0};
    int ep = epoll_create1(0);
    int tfd = timerfd_create(CLOCK_MONOTONIC, TFD_NONBLOCK);
    struct epoll_event tev;
    memset(&tev, 0, sizeof tev);
    tev.events = EPOLLIN;
    tev.data.ptr = NULL;
    epoll_ctl(ep, EPOLL_CTL_ADD, tfd, &tev);

    conn_t *conns = calloc((size_t)n_conns, sizeof(conn_t));
    conn_t **idle = calloc((size_t)n_conns, sizeof(conn_t *));
    int n_idle = 0;
    for (int i = 0; i < n_conns; i++) {
        conns[i].req = -1;
        conns[i].fd = dial(port);
        if (conns[i].fd < 0) {
            for (int j = 0; j < i; j++) close(conns[j].fd);
            free(conns);
            free(idle);
            close(tfd);
            close(ep);
            return -1;
        }
        watch(ep, &conns[i], EPOLLIN, EPOLL_CTL_ADD);
        idle[n_idle++] = &conns[i];
    }
    for (long long i = 0; i < n_req; i++) {
        status[i] = 0;
        send_ns[i] = done_ns[i] = -1;
        shaped[i] = 0;
        if (keep_off) keep_off[2 * i] = keep_off[2 * i + 1] = -1;
    }

    const int closed = due_ns == NULL;
    const int64_t window_ns = (int64_t)(seconds * 1e9);
    const int64_t drain_ns = (int64_t)(drain_s * 1e9);
    long long next = 0, out = 0;
    r.t0 = now_ns();
    *out_t0 = r.t0;
    int64_t stop_at = -1;   /* set once the last request is sent */
    struct epoll_event evs[128];

    for (;;) {
        int64_t t = now_ns() - r.t0;
        /* send what is due */
        while (next < n_req && n_idle > 0) {
            if (closed ? t >= window_ns : due_ns[next] > t) break;
            conn_t *c = idle[--n_idle];
            if (c->fd < 0) { continue; }
            start_req(&r, c, next++, ep);
            out++;
        }
        int sending_over = next >= n_req || (closed && t >= window_ns);
        if (sending_over && stop_at < 0) stop_at = t + drain_ns;
        if (out == 0 && sending_over) break;
        if (stop_at >= 0 && t >= stop_at) break;
        /* sleep until the next due time or an event */
        int64_t wake = -1;
        if (!closed && next < n_req && n_idle > 0) wake = due_ns[next];
        else if (stop_at >= 0) wake = stop_at;
        else if (closed) wake = window_ns;
        if (wake >= 0) {
            struct itimerspec its;
            memset(&its, 0, sizeof its);
            int64_t abs_ns = r.t0 + (wake > t ? wake : t + 1000);
            its.it_value.tv_sec = abs_ns / 1000000000LL;
            its.it_value.tv_nsec = abs_ns % 1000000000LL;
            timerfd_settime(tfd, TFD_TIMER_ABSTIME, &its, NULL);
        }
        int n = epoll_wait(ep, evs, 128, 1000);
        for (int k = 0; k < n; k++) {
            conn_t *c = evs[k].data.ptr;
            if (c == NULL) {
                uint64_t x;
                while (read(tfd, &x, sizeof x) > 0) {}
                continue;
            }
            if (c->fd < 0) continue;
            if (evs[k].events & (EPOLLERR | EPOLLHUP)) {
                if (c->req >= 0) out--;
                fail(&r, c, ep, port);
                if (c->fd >= 0) idle[n_idle++] = c;
                continue;
            }
            if (c->req < 0) {
                /* an idle connection only becomes readable when the
                 * server closes it */
                char tmp[256];
                if (read(c->fd, tmp, sizeof tmp) == 0) {
                    for (int j = 0; j < n_idle; j++)
                        if (idle[j] == c) { idle[j] = idle[--n_idle]; break; }
                    fail(&r, c, ep, port);
                    if (c->fd >= 0) idle[n_idle++] = c;
                }
                continue;
            }
            if (evs[k].events & EPOLLOUT) {
                int w = pump_write(c);
                if (w < 0) {
                    out--;
                    fail(&r, c, ep, port);
                    if (c->fd >= 0) idle[n_idle++] = c;
                    continue;
                }
                if (w == 1) watch(ep, c, EPOLLIN, EPOLL_CTL_MOD);
            }
            if (evs[k].events & EPOLLIN) {
                int rd = pump_read(c);
                if (rd < 0) {
                    out--;
                    fail(&r, c, ep, port);
                    if (c->fd >= 0) idle[n_idle++] = c;
                } else if (rd == 1) {
                    finish(&r, c);
                    out--;
                    idle[n_idle++] = c;
                }
            }
        }
    }
    for (int i = 0; i < n_conns; i++) {
        if (conns[i].fd >= 0) close(conns[i].fd);
        free(conns[i].rbuf);
    }
    free(conns);
    free(idle);
    close(tfd);
    close(ep);
    return next;
}
