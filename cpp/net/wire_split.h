// The wire phase of a call, cut at the server's stamps.
//
// A tstd response carries the server's arrival, handler-entry and done
// readings of ITS monotonic clock (RpcMeta::srv; server.cc stamps them,
// channel.cc keeps them in Controller::CallState).  With the caller's own
// issue and reply readings they cut "request out, server, response in"
// into parts, and this is the one place that says how:
//
//   issue ......... arrival ... handler ... done ......... reply
//   |--- req_leg ---|-- queue --|- handler -|--- resp_leg ---|
//
// queue and handler are differences of one clock (the server's) and net =
// wire - (done - arrival) is a difference of differences, so the three
// hold whatever the peer's clock reads.  The two legs need arrival on the
// caller's clock: they are cut only where both ends read one clock (the
// caller says so; channel.cc decides it from the connection).  Then
// req_leg + queue + handler + (net - req_leg) = wire exactly, integers on
// one clock: the identity that checks the stamps.
#pragma once

#include <cstdint>

namespace trpc {

// The server's three readings that ride a response back (its monotonic
// clock, us): the request whole, its handler entered, the handler's
// done() entered.  All zero: none carried.
struct SrvStamps {
  int64_t arrival_us = 0;
  int64_t handler_us = 0;
  int64_t done_us = 0;
};

struct WireSplit {
  bool split = false;  // the response carried stamps that fit the call
  bool legs = false;   // ...and arrival could be read on the caller's clock
  int64_t srv_queue_us = 0;    // request whole -> handler entered
  int64_t srv_handler_us = 0;  // handler entered -> its done() ran
  int64_t net_us = 0;          // wire less the server's share: both legs
  int64_t req_leg_us = 0;      // issue -> request whole (legs only)
};

// issue_us / reply_us: the caller's readings around the call (just before
// CallMethod, entry of its completion).  srv: the response's stamps.
// Stamps that cannot be this call's (out of order, or a server share
// longer than the whole wire: a hostile or broken peer) split nothing; an
// arrival outside [issue, reply - server share] on a connection said to
// share a clock cuts no leg.
inline WireSplit split_wire(int64_t issue_us, int64_t reply_us,
                            const SrvStamps& srv, bool same_clock) {
  WireSplit w;
  const int64_t wire_us = reply_us - issue_us;
  const int64_t srv_us = srv.done_us - srv.arrival_us;
  if (srv.arrival_us == 0 || srv.handler_us < srv.arrival_us ||
      srv.done_us < srv.handler_us || wire_us < 0 || srv_us > wire_us) {
    return w;
  }
  w.split = true;
  w.srv_queue_us = srv.handler_us - srv.arrival_us;
  w.srv_handler_us = srv.done_us - srv.handler_us;
  w.net_us = wire_us - srv_us;
  const int64_t req_leg_us = srv.arrival_us - issue_us;
  if (same_clock && req_leg_us >= 0 && req_leg_us <= w.net_us) {
    w.legs = true;
    w.req_leg_us = req_leg_us;
  }
  return w;
}

}  // namespace trpc
